"""Fused segmented arena scan (DESIGN.md §3.9).

One kernel, two implementations sharing one chunking schedule:

* :func:`_pallas_fused_scan` — a Pallas TPU kernel over a
  ``(Q // queries_per_tile, span // rows_per_chunk)`` grid.  Each grid
  step DMAs one chunk of the per-query candidate-id window from the CSR
  row table (HBM → SMEM), gathers for each id one 128-lane sidecar row —
  label words, norm, liveness, and for the int8 tier the packed codes
  and scale/zero-point (:func:`row_sidecar`) — plus the f32/fp16 code
  row, with per-row async copies (HBM → VMEM), dequantizes in-register
  with the ``dcols`` column mask, computes multiply +
  minor-axis-reduce distances, applies the packed-label + tombstone +
  segment-length filter, and merges the chunk into a running (distance,
  position) top-k held in VMEM scratch across chunks.  The ``[Q, span]``
  distance matrix never exists anywhere.

* :func:`_lax_fused_scan` — the interpret/CPU fallback: the same chunk
  schedule composed from ``jax.lax`` (a ``lax.map`` over query tiles of a
  ``lax.scan`` over row chunks), arithmetically byte-identical to the
  unfused executor's ref branch.

Both are bit-compatible with the unchunked oracle
``ref.segmented_filtered_topk``: distances are the same multiply +
minor-axis f32 reduce (never ``dot_general``), and the running-pool merge
preserves the (distance, position) lexicographic order for ANY chunk /
query-tile decomposition — chunk entries always carry strictly later
positions than the running pool, and every selection step prefers the
lower concatenation index on value ties, exactly like ``lax.top_k`` in
the unfused scan.  Tile sizes come from the roofline model
(``launch/roofline.py::fused_scan_tiles``), not hand constants.

Dispatched behind ``ops._segmented_topk`` via the ``fused`` flag; see
DESIGN.md §3.9 for the contract and docs/KERNELS.md for the authoring
walkthrough.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

# Mosaic's tile size for a 1-D 32-bit array in HBM: a DMA may slice such an
# array only at multiples of it (the row table's id windows, below)
ID_ALIGN = 1024


def resolve_fused(fused, *, backend: str) -> bool:
    """Resolve the public ``fused=True|False|"auto"`` flag to a static
    bool.  ``"auto"`` enables the fused kernel wherever the pallas gather
    path would run (the fused kernel strictly dominates the per-candidate
    gather kernel there) and keeps the ref/lax executor unfused by
    default — its win is workload-dependent, so opting in is explicit."""
    if fused == "auto":
        return backend == "pallas"
    if fused in (True, False):
        return bool(fused)
    raise ValueError(f"fused must be True, False or 'auto'; got {fused!r}")


def clamp_qtile(qtile: int, q: int) -> int:
    """Largest power-of-two ≤ ``qtile`` that divides ``q`` (engine buckets
    are powers of two, so this is usually ``min(qtile, q)``; direct kernel
    callers with odd Q degrade toward per-query tiles)."""
    qtile = max(1, min(qtile, q))
    while q % qtile:
        qtile //= 2
    return max(1, qtile)


def fused_segmented_scan(q, lq, ax, alw, axn, rows_concat, starts, lens,
                         tomb, scales, zeros, *, kp: int, lmax: int,
                         chunk: int, qtile: int, metric: str, dtype: str,
                         dcols: int | None, backend: str, interpret: bool):
    """Scan stage of the fused path: (vals [Q, kp] asc, pos [Q, kp] i32,
    pos == lmax ⇒ empty).  The caller (``ops._segmented_topk``) owns the
    rerank stage and the empty-slot/gid epilogue, shared with the unfused
    executor."""
    if lmax % chunk:
        raise ValueError(f"chunk {chunk} must divide lmax {lmax}")
    if backend == "pallas":
        return _pallas_fused_scan(
            q, lq, ax, alw, axn, rows_concat, starts, lens, tomb, scales,
            zeros, kp=kp, lmax=lmax, chunk=chunk, qtile=qtile,
            metric=metric, dtype=dtype, dcols=dcols, interpret=interpret)
    return _lax_fused_scan(
        q, lq, ax, alw, axn, rows_concat, starts, lens, tomb, scales,
        zeros, kp=kp, lmax=lmax, chunk=chunk, qtile=qtile, metric=metric,
        dtype=dtype)


# ---------------------------------------------------------------------------
# lax-composed fallback (CPU / interpret), same schedule
# ---------------------------------------------------------------------------


def _lax_fused_scan(q, lq, ax, alw, axn, rows_concat, starts, lens, tomb,
                    scales, zeros, *, kp, lmax, chunk, qtile, metric,
                    dtype):
    Q = q.shape[0]
    R = rows_concat.shape[0]
    qtile = clamp_qtile(qtile, Q)
    steps = jnp.arange(0, lmax, chunk, dtype=jnp.int32)

    def tile_fn(tile):
        qt, lqt, st, ln = tile
        qn = jnp.sum(qt * qt, axis=1)
        init = (jnp.full((qtile, kp), jnp.inf, jnp.float32),
                jnp.full((qtile, kp), lmax, jnp.int32))

        def body(carry, c0):
            run_v, run_p = carry
            pos = c0 + jnp.arange(chunk, dtype=jnp.int32)        # [C]
            valid = pos[None, :] < ln[:, None]                   # [T, C]
            p = jnp.clip(st[:, None] + pos[None, :], 0, max(R - 1, 0))
            gid = rows_concat[jnp.where(valid, p, 0)]            # [T, C]
            xg = ref.dequantize_rows(
                ax[gid], dtype,
                None if scales is None else scales[gid],
                None if zeros is None else zeros[gid])           # [T, C, D]
            # multiply + minor-axis reduce, NOT dot_general: per-element
            # f32 accumulation, independent of the (qtile, chunk) tiling —
            # the bit-parity the fused/unfused equivalence rests on
            ip = jnp.sum(xg * qt[:, None, :], axis=-1)
            d = -ip if metric == "ip" else \
                qn[:, None] - 2.0 * ip + axn[gid]
            keep = jnp.all((lqt[:, None, :] & alw[gid]) == lqt[:, None, :],
                           axis=-1)
            if tomb is not None:
                keep = keep & ref.tombstone_mask(tomb, gid)
            d = jnp.where(keep & valid, d, jnp.inf)
            # running-pool merge: running entries hold strictly earlier
            # positions and lax.top_k prefers the lower concat index on
            # ties, preserving (distance, position) order chunk by chunk
            cat_v = jnp.concatenate([run_v, d], axis=1)
            cat_p = jnp.concatenate(
                [run_p, jnp.broadcast_to(pos[None, :], (qtile, chunk))],
                axis=1)
            neg, sel = jax.lax.top_k(-cat_v, kp)
            return (-neg, jnp.take_along_axis(cat_p, sel, axis=1)), None

        (v, p), _ = jax.lax.scan(body, init, steps)
        return v, p

    tiles = (q.reshape(Q // qtile, qtile, -1),
             lq.reshape(Q // qtile, qtile, -1),
             jnp.asarray(starts).reshape(Q // qtile, qtile),
             jnp.asarray(lens).reshape(Q // qtile, qtile))
    v, p = jax.lax.map(tile_fn, tiles)
    return v.reshape(Q, kp), p.reshape(Q, kp)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


SIDE_LANES = 128   # one lane tile: the width of the per-row sidecar


def row_sidecar(alw, norms=None, scales=None, zeros=None, tomb=None,
                codes=None):
    """Per-row operands of the Pallas scan as one [N, SIDE_LANES] i32
    array, plus its static lane layout ``((field, first lane, width),
    ...)``.

    Mosaic slices a 2-D HBM array in whole 128-lane tiles, and a 1-row
    slice only where the array's tile is one row high (32-bit arrays).  So
    narrow per-row arrays (label words, the norm, the int8 scale and
    zero-point, liveness) and the uint8 codes of the int8 tier could not be
    gathered row by row on their own; side by side as 32-bit lanes of one
    row they cost one DMA per row.  ``codes`` (uint8 [N, Dp], Dp a multiple
    of 4) are packed four to a word, little-endian: lane i holds codes 4i
    .. 4i+3 (see :func:`pack_query`).  f32 bits ride as i32; liveness is
    1 for a live row."""
    n = alw.shape[0]
    cols, layout, lane = [], [], 0

    def add(name, col):
        nonlocal lane
        col = col.astype(jnp.int32) if col.dtype != jnp.float32 else \
            jax.lax.bitcast_convert_type(col, jnp.int32)
        col = col.reshape(n, -1)
        layout.append((name, lane, col.shape[1]))
        cols.append(col)
        lane += col.shape[1]

    if codes is not None:
        words = jax.lax.bitcast_convert_type(
            codes.reshape(n, -1, 4), jnp.uint32)
        add("codes", jax.lax.bitcast_convert_type(words, jnp.int32))
    add("labels", alw)
    for name, col in (("norm", norms), ("scale", scales), ("zero", zeros)):
        if col is not None:
            add(name, col.astype(jnp.float32))
    if tomb is not None:
        add("alive", ref.tombstone_mask(tomb, jnp.arange(n, dtype=jnp.int32)))
    if lane > SIDE_LANES:
        raise ValueError(f"{lane} sidecar lanes exceed {SIDE_LANES}")
    side = jnp.concatenate(cols, axis=1)
    return jnp.pad(side, ((0, 0), (0, SIDE_LANES - lane))), tuple(layout)


def pack_query(q, layout):
    """Reorder query columns to match codes packed by :func:`row_sidecar`:
    the kernel unpacks byte b of every code word into one lane block, so
    block b must meet query columns b, b+4, b+8, ..."""
    if not any(f == "codes" for f, _, _ in layout):
        return q
    return jnp.concatenate([q[:, b::4] for b in range(4)], axis=1)


def id_window(chunk: int) -> int:
    """Words one aligned id-window DMA copies to cover ``chunk`` ids that
    may start anywhere: whole ID_ALIGN tiles, one more than ``chunk``
    needs."""
    return -(-chunk // ID_ALIGN) * ID_ALIGN + ID_ALIGN


def pad_id_table(ids, chunk: int):
    """Pad a 1-D id table so that the aligned window of :func:`id_window`
    words that covers ``chunk`` ids from any start up to ``len`` exists.
    Returns (padded table, largest such start)."""
    ids = jnp.asarray(ids, jnp.int32)
    r_eff = max(ids.shape[0], chunk)
    rp = -(-r_eff // ID_ALIGN) * ID_ALIGN + id_window(chunk)
    return jnp.pad(ids, (0, rp - ids.shape[0])), r_eff


def dma_id_windows(ids_ref, idbuf, sem, starts, win):
    """Copy, for each tile row t, the aligned window of ``ids_ref`` (a 1-D
    i32 HBM table) that covers [starts[t], starts[t] + chunk) into SMEM at
    ``t * win``.  Mosaic slices such a table only at whole ID_ALIGN tiles,
    hence the aligned window.  Returns each row's SMEM offset of id 0."""
    cps, offs = [], []
    for t, cs in enumerate(starts):
        a0 = pl.multiple_of((cs // ID_ALIGN) * ID_ALIGN, ID_ALIGN)
        offs.append(t * win + cs - a0)
        cps.append(pltpu.make_async_copy(
            ids_ref.at[pl.ds(a0, win)], idbuf.at[pl.ds(t * win, win)], sem))
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()
    return offs


def gather_rows(idbuf, offs, counts, side_ref, sbuf, ax_ref, xbuf, sem):
    """Per-row gather DMAs for tile row t's first ``counts[t]`` ids
    (sidecar, and codes unless they ride in the sidecar: ``ax_ref`` None),
    all in flight before the first wait (the DMA engine pipelines them).
    Rows past a query's segment are not fetched: their buffer lanes keep
    stale values, which the caller's ``pos < len`` mask discards.  Issued
    from loops, not unrolled, so the kernel's size does not grow with the
    tile."""
    def copies(t, r, rid):
        cps = [pltpu.make_async_copy(side_ref.at[pl.ds(rid, 1), :],
                                     sbuf.at[t, pl.ds(r, 1), :], sem)]
        if ax_ref is not None:
            cps.append(pltpu.make_async_copy(ax_ref.at[pl.ds(rid, 1), :],
                                             xbuf.at[t, pl.ds(r, 1), :], sem))
        return cps

    for t, (off, n) in enumerate(zip(offs, counts)):
        def start(r, carry, t=t, off=off):
            for cp in copies(t, r, idbuf[off + r]):
                cp.start()
            return carry
        jax.lax.fori_loop(0, n, start, 0)

    def wait(i, carry):
        # a wait consumes one copy's byte count; every row copy of a kind
        # moves the same bytes, so any row's descriptor stands for it
        for cp in copies(0, 0, 0):
            cp.wait()
        return carry
    jax.lax.fori_loop(0, sum(counts), wait, 0)


def filtered_distances(qv, lqv, xr, sv, layout, *, metric, dtype, dcols):
    """Dequant + distance + label/tombstone filter of one gathered chunk,
    all in registers: ``qv`` [T, Dp] f32 (:func:`pack_query` order),
    ``lqv`` [T, W], ``xr`` [T, C, Dp] codes (None when they ride in the
    sidecar), ``sv`` [T, C, SIDE_LANES] sidecar rows -> [T, C] f32, +inf
    where filtered out."""
    lanes = {f: (a, w) for f, a, w in layout}

    def field(name):
        a, w = lanes[name]
        return sv[:, :, a:a + w]

    def f32_field(name):
        return jax.lax.bitcast_convert_type(field(name), jnp.float32)

    if dtype == "int8":
        # byte b of code word i is column 4i + b: unpack each byte into one
        # lane block (u8 -> i32 -> f32 is exact; Mosaic casts no u8 -> f32)
        words = field("codes")                              # [T, C, Dp/4]
        nw = words.shape[2]
        zero, scale = f32_field("zero"), f32_field("scale")
        col = 4 * jax.lax.broadcasted_iota(jnp.int32, words.shape, 2)
        ip = 0.0
        for b in range(4):
            code = jax.lax.shift_right_logical(words, 8 * b) & 0xFF
            xb = zero + scale * code.astype(jnp.float32)
            if dcols is not None:
                # lane-pad code byte 0 dequantizes to the row zero-point,
                # not 0 — mask the pad columns (DESIGN.md §3.9)
                xb = jnp.where(col + b < dcols, xb, 0.0)
            qb = jax.lax.slice_in_dim(qv, b * nw, (b + 1) * nw, axis=1)
            ip = ip + jnp.sum(xb * qb[:, None, :], axis=-1)
    else:
        if dtype == "fp16":
            xr = xr.astype(jnp.float32)
        ip = jnp.sum(xr * qv[:, None, :], axis=-1)          # [T, C]
    if metric == "ip":
        d = -ip
    else:
        qn = jnp.sum(qv * qv, axis=1)
        d = qn[:, None] - 2.0 * ip + f32_field("norm")[:, :, 0]
    lx = field("labels")
    keep = jnp.all((lqv[:, None, :] & lx) == lqv[:, None, :], axis=-1)
    if "alive" in lanes:
        keep = keep & (field("alive")[:, :, 0] != 0)
    return jnp.where(keep, d, jnp.inf)


def scan_operands(ax, alw, norms, scales, zeros, tomb, *, dtype):
    """(codes operand or None, sidecar, layout) of one Pallas scan: the
    int8 tier's codes ride in the sidecar, other tiers' rows are gathered
    from ``ax`` itself."""
    packed = dtype == "int8"
    side, layout = row_sidecar(alw, norms, scales if packed else None,
                               zeros if packed else None, tomb,
                               codes=ax if packed else None)
    return (None if packed else ax), side, layout


def _pallas_fused_scan(q, lq, ax, alw, axn, rows_concat, starts, lens,
                       tomb, scales, zeros, *, kp, lmax, chunk, qtile,
                       metric, dtype, dcols, interpret):
    Q, Dp = q.shape
    qtile = clamp_qtile(qtile, Q)
    nc = lmax // chunk
    win = id_window(chunk)
    # a chunk that holds rows of its segment starts below the table's end,
    # so its window is read where it lies; starts past the end (chunks past
    # a segment, which gather no rows) are clamped to stay in range
    rc, max_start = pad_id_table(rows_concat, chunk)
    ax, side, layout = scan_operands(
        ax, alw, axn if metric == "l2" else None, scales, zeros, tomb,
        dtype=dtype)
    q = pack_query(q, layout)

    scratch = [
        pltpu.SMEM((qtile * win,), jnp.int32),             # id windows
        pltpu.VMEM((qtile, chunk, SIDE_LANES), jnp.int32),  # gathered sidecar
        pltpu.VMEM((qtile, kp), jnp.float32),              # running vals
        pltpu.VMEM((qtile, kp), jnp.int32),                # running pos
        pltpu.SemaphoreType.DMA,
    ]
    operands = [rc, side]
    if ax is not None:
        scratch.append(pltpu.VMEM((qtile, chunk, Dp), ax.dtype))  # codes
        operands.append(ax)

    def kernel(starts_sm, lens_sm, q_ref, lq_ref, rc_ref, side_ref, *rest):
        if ax is None:
            ax_ref, xbuf = None, None
            vals_ref, pos_ref, idbuf, sbuf, run_v, run_p, sem = rest
        else:
            (ax_ref, vals_ref, pos_ref, idbuf, sbuf, run_v, run_p, sem,
             xbuf) = rest
        ti = pl.program_id(0)
        ci = pl.program_id(1)
        c0 = ci * chunk

        @pl.when(ci == 0)
        def _init():
            run_v[...] = jnp.full((qtile, kp), jnp.inf, jnp.float32)
            run_p[...] = jnp.full((qtile, kp), lmax, jnp.int32)

        # -- phases 1-3: id windows (contiguous CSR slices), row gather,
        # dequant + distance + filter --
        offs = dma_id_windows(
            rc_ref, idbuf, sem,
            [jnp.clip(starts_sm[ti * qtile + t] + c0, 0, max_start)
             for t in range(qtile)], win)
        lens_t = [lens_sm[ti * qtile + t] for t in range(qtile)]
        gather_rows(idbuf, offs,
                    [jnp.clip(n - c0, 0, chunk) for n in lens_t],
                    side_ref, sbuf, ax_ref, xbuf, sem)
        d = filtered_distances(
            q_ref[...], lq_ref[...], None if xbuf is None else xbuf[...],
            sbuf[...], layout, metric=metric, dtype=dtype, dcols=dcols)
        lens_vec = jnp.stack(lens_t)
        pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (qtile, chunk), 1)
        d = jnp.where(pos < lens_vec[:, None], d, jnp.inf)

        # -- phase 4: merge the chunk into the VMEM-resident running
        # top-k.  Iterative first-min selection over [running | chunk]
        # reproduces lax.top_k's (value, concat-index) order bitwise:
        # the first unselected lane holding the minimum wins, so value
        # ties resolve toward the running pool (strictly earlier
        # positions), and surviving +inf slots keep the running pool's
        # pos == lmax sentinel — the invariant the rerank stage's
        # ``listed`` mask depends on --
        m_lanes = kp + chunk
        cat_v = jnp.concatenate([run_v[...], d], axis=1)
        cat_p = jnp.concatenate([run_p[...], pos], axis=1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (qtile, m_lanes), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (qtile, kp), 1)
        taken = jnp.zeros((qtile, m_lanes), jnp.bool_)
        new_v = jnp.zeros((qtile, kp), jnp.float32)
        new_p = jnp.zeros((qtile, kp), jnp.int32)
        for j in range(kp):
            vm = jnp.where(taken, jnp.inf, cat_v)
            m = jnp.min(vm, axis=1)
            cand = (~taken) & (vm == m[:, None])
            first = jnp.min(jnp.where(cand, lane, m_lanes), axis=1)
            hit = lane == first[:, None]
            pj = jnp.sum(jnp.where(hit, cat_p, 0), axis=1)
            new_v = jnp.where(col == j, m[:, None], new_v)
            new_p = jnp.where(col == j, pj[:, None], new_p)
            taken = taken | hit
        run_v[...] = new_v
        run_p[...] = new_p

        @pl.when(ci == nc - 1)
        def _emit():
            vals_ref[...] = run_v[...]
            pos_ref[...] = run_p[...]

    def im(i, j, starts_ref, lens_ref):
        return (i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q // qtile, nc),
        in_specs=[pl.BlockSpec((qtile, Dp), im),
                  pl.BlockSpec((qtile, lq.shape[1]), im)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
        out_specs=[pl.BlockSpec((qtile, kp), im),
                   pl.BlockSpec((qtile, kp), im)],
        scratch_shapes=scratch,
    )
    vals, pos = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Q, kp), jnp.float32),
                   jax.ShapeDtypeStruct((Q, kp), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32),
      q, lq, *operands)
    return vals, pos
