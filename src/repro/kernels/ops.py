"""Public jit'd wrappers around the Pallas kernels.

Handles shape padding (queries → block_q, rows → block_n, features → 128
lanes), backend selection (compiled Pallas on TPU, interpret mode
elsewhere, pure-jnp `ref` as an escape hatch), and int32 label-word layout.

All functions take *unpadded* arrays and return unpadded results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.labels import masks_to_int32_words
from ..obs import metrics as _metrics
from . import ref
from .filtered_topk import filtered_topk_pallas
from .fused_scan import (fused_segmented_scan, resolve_fused, row_sidecar,
                         scan_operands)
from .gather_distance import (gather_distance_pallas,
                              segmented_gather_distance_pallas)
from .masked_distance import LABEL_WORDS, masked_distance_pallas


def default_interpret() -> bool:
    """Pallas interpret mode: compiled on TPU, interpreted on CPU/GPU."""
    return jax.default_backend() != "tpu"


def _pad_axis(a: jnp.ndarray, axis: int, mult: int, value=0):
    size = a.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, target - size)
    return jnp.pad(a, widths, constant_values=value)


def prepare_label_words(masks_u64: np.ndarray) -> np.ndarray:
    """(N, NUM_WORDS) uint64 -> (N, LABEL_WORDS) int32 device layout."""
    return masks_to_int32_words(np.asarray(masks_u64, dtype=np.uint64))


def masked_distance(q, x, lq_words, lx_words, *, metric: str = "l2",
                    block_q: int = 8, block_n: int = 512,
                    backend: str = "pallas") -> jnp.ndarray:
    """[Q, D] x [N, D] (+ label words) -> [Q, N] f32 masked distances."""
    if backend == "ref":
        return ref.masked_distance(q, x, lq_words, lx_words, metric)
    Q, N = q.shape[0], x.shape[0]
    block_n = min(block_n, max(128, 1 << (N - 1).bit_length()))
    qp = _pad_axis(_pad_axis(q, 1, 128), 0, block_q)
    xp = _pad_axis(_pad_axis(x, 1, 128), 0, block_n)
    lqp = _pad_axis(jnp.asarray(lq_words, jnp.int32), 0, block_q)
    lxp = _pad_axis(jnp.asarray(lx_words, jnp.int32), 0, block_n)
    out = masked_distance_pallas(qp, xp, lqp, lxp, metric=metric,
                                 block_q=block_q, block_n=block_n,
                                 n_total=N, interpret=default_interpret())
    return out[:Q, :N]


def filtered_topk(q, x, lq_words, lx_words, *, k: int, metric: str = "l2",
                  block_q: int = 8, block_n: int = 512,
                  backend: str = "pallas", tomb=None):
    """Fused filtered top-k: -> (vals [Q, k], idxs [Q, k]); idx == N ⇒ pad.

    ``tomb`` (optional packed bitmap [⌈N/8⌉] u8, DESIGN.md §3.6): set bits
    drop rows from the result exactly like a failed label containment.  On
    the pallas path the gathered-byte AND composes outside the fused
    kernel (distances from the masked-distance kernel, mask + ``lax.top_k``
    at the jnp level); ``tomb=None`` keeps the fused program untouched.
    """
    if backend == "ref":
        return ref.filtered_topk(q, x, lq_words, lx_words, k, metric,
                                 tomb=tomb)
    if tomb is not None:
        d = masked_distance(q, x, lq_words, lx_words, metric=metric,
                            block_q=block_q, block_n=block_n, backend=backend)
        return _masked_distance_topk(d, jnp.asarray(tomb), x.shape[0], k=k)
    Q, N = q.shape[0], x.shape[0]
    block_n = min(block_n, max(128, 1 << (N - 1).bit_length()))
    k_eff = min(k, block_n)
    qp = _pad_axis(_pad_axis(q, 1, 128), 0, block_q)
    xp = _pad_axis(_pad_axis(x, 1, 128), 0, block_n)
    lqp = _pad_axis(jnp.asarray(lq_words, jnp.int32), 0, block_q)
    lxp = _pad_axis(jnp.asarray(lx_words, jnp.int32), 0, block_n)
    vals, idxs = filtered_topk_pallas(qp, xp, lqp, lxp, k=k_eff, metric=metric,
                                      block_q=block_q, block_n=block_n,
                                      n_total=N, interpret=default_interpret())
    vals, idxs = vals[:Q], idxs[:Q]
    if k_eff < k:  # degenerate tiny-index case: pad out to k
        vals = jnp.pad(vals, ((0, 0), (0, k - k_eff)), constant_values=jnp.inf)
        idxs = jnp.pad(idxs, ((0, 0), (0, k - k_eff)), constant_values=N)
    return vals, idxs


def masked_topk_tail(d, tomb, n: int, *, k: int):
    """Shared epilogue for every flat masked-distance top-k path: the
    optional tombstone AND over the row iota, the k > n inf-pad, the
    deterministic (distance, index) ``lax.top_k``, and the (+inf, n)
    empty-slot normalization.  ONE home for the tie-break/sentinel
    convention — the flat ref program (`index/flat.py`) and the
    pallas-path composition below both delegate here, so the two cannot
    silently diverge.  Traceable (called inside jit)."""
    if tomb is not None:
        alive = ref.tombstone_mask(tomb, jnp.arange(n, dtype=jnp.int32))
        d = jnp.where(alive[None, :], d, jnp.inf)
    if k > n:  # fewer rows than requested: pad the distance matrix
        d = jnp.pad(d, ((0, 0), (0, k - n)), constant_values=jnp.inf)
    neg, idxs = jax.lax.top_k(-d, k)
    vals = -neg
    idxs = jnp.where(jnp.isinf(vals), n, idxs)
    vals = jnp.where(jnp.isinf(vals), jnp.float32(jnp.inf), vals)
    return vals, idxs.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "k"))
def _masked_distance_topk(d, tomb, n: int, *, k: int):
    """Tombstone-mask a [Q, N] distance matrix and take the deterministic
    (distance, index) top-k — the pallas-path composition of
    :func:`filtered_topk` with a tombstone bitmap."""
    return masked_topk_tail(d, tomb, n, k=k)


# Candidate-span chunk for the segmented arena scan: bounds the gathered
# [Q, chunk, D] working set (and, on the pallas path, the SMEM id table)
# while keeping the chunk count static per (k, bucket, lmax) program.
SEG_CHUNK = 2048


@functools.partial(jax.jit, static_argnames=("k", "lmax", "chunk", "metric",
                                             "backend", "interpret", "dtype",
                                             "kprime", "dcols", "fused",
                                             "qtile"))
def _segmented_topk(q, lq, ax, alw, axn, rows_concat, starts, lens,
                    tomb=None, scales=None, zeros=None, rr=None, rrn=None, *,
                    k: int, lmax: int, chunk: int, metric: str, backend: str,
                    interpret: bool, dtype: str = "f32",
                    kprime: int | None = None, dcols: int | None = None,
                    fused: bool = False, qtile: int | None = None):
    """Chunked segmented arena top-k — bit-identical to the unchunked
    oracle ``ref.segmented_filtered_topk``.

    The candidate span [0, lmax) is scanned in static chunks with a running
    (vals, pos) top-k.  The merge concatenates [running, chunk] before
    ``lax.top_k``: running entries hold strictly earlier positions, and
    XLA's TopK breaks value ties toward the lower concatenation index, so
    the (distance, position) lexicographic order of the full-span top-k is
    preserved chunk by chunk (the running pool stays sorted by exactly that
    order — the induction the parity tests pin down).

    ``tomb`` (optional, DESIGN.md §3.6): packed tombstone bitmap [⌈N/8⌉]
    u8 whose set bits drop rows from the keep mask — one extra AND fused
    into the existing label filter, touching no distance value and adding
    no dispatch key (``None``, the static engine's setting, traces the
    mutation-free program exactly as before).

    Tiered precision (DESIGN.md §3.8): ``dtype`` selects the scan tier —
    ``"f32"`` (the default) runs byte-for-byte today's program;
    ``"fp16"``/``"int8"`` scan dequantized codes (int8 gathers the per-row
    ``scales``/``zeros`` alongside, and on the pallas backend the codes
    travel packed four to a 32-bit word, one byte per code).  With a rerank tier (``rr``/``rrn``, the exact
    f32 rows + norms) the scan instead keeps a k' = ``kprime`` shortlist
    which a second in-program stage reranks exactly: the shortlist is
    re-sorted by segment position (so ``lax.top_k``'s lower-index
    tie-break reproduces the (distance, position) lexicographic order of
    the single-level program), exact distances are gathered from the
    rerank tier, and the final top-k comes out of the SAME traced program
    — one dispatch per (k, Q-bucket, span tier, dtype), and warmup covers
    scan + rerank together.
    """
    Q = q.shape[0]
    R = rows_concat.shape[0]
    if lmax % chunk:
        raise ValueError(f"chunk {chunk} must divide lmax {lmax}")
    if metric not in ("l2", "ip"):
        raise ValueError(f"unknown metric {metric!r}")
    # shortlist width: k' bounded by the span (a span-sized shortlist is
    # already exhaustive), never below k (the output width)
    kp = k if rr is None else max(k, min(kprime or 4 * k, lmax))
    with jax.named_scope("scan.distance"):
        qn = jnp.sum(q * q, axis=1)
    init = (jnp.full((Q, kp), jnp.inf, jnp.float32),
            jnp.full((Q, kp), lmax, jnp.int32))

    if backend == "pallas" and not fused:
        with jax.named_scope("scan.sidecar"):
            sx, side, layout = scan_operands(
                ax, alw, axn if metric == "l2" else None, scales, zeros,
                tomb, dtype=dtype)

    def body(carry, c0):  # unfused scan stage (fused=False)
        run_v, run_p = carry
        with jax.named_scope("scan.row_ids"):
            pos = c0 + jnp.arange(chunk, dtype=jnp.int32)      # [C]
            valid = pos[None, :] < lens[:, None]               # [Q, C]
            p = jnp.clip(starts[:, None] + pos[None, :], 0, max(R - 1, 0))
            gid = rows_concat[jnp.where(valid, p, 0)]          # [Q, C]
        if backend == "pallas":
            # the kernel fuses label + tombstone filter and the lens mask
            with jax.named_scope("scan.rows"):
                d = segmented_gather_distance_pallas(
                    q, lq, sx, side, gid, jnp.clip(lens - c0, 0, chunk),
                    layout=layout, metric=metric, dtype=dtype,
                    interpret=interpret, dcols=dcols)
        else:
            with jax.named_scope("scan.rows"):
                xg = ref.dequantize_rows(
                    ax[gid], dtype,
                    None if scales is None else scales[gid],
                    None if zeros is None else zeros[gid])     # [Q, C, D]
            # explicit multiply + minor-axis reduce, NOT a dot_general: XLA
            # tiles batched contractions differently per batch size, which
            # perturbs f32 accumulation order at ULP level — a reduce over
            # the contiguous minor dim is per-element and therefore
            # batch-composition independent, which the executor's
            # bit-parity contract (batched == looped) depends on
            with jax.named_scope("scan.distance"):
                ip = jnp.sum(xg * q[:, None, :], axis=-1)
                d = -ip if metric == "ip" else qn[:, None] - 2.0 * ip
            if metric == "l2":
                with jax.named_scope("scan.norms"):
                    xn = axn[gid]
                with jax.named_scope("scan.distance"):
                    d = d + xn
            with jax.named_scope("scan.label_words"):
                keep = jnp.all((lq[:, None, :] & alw[gid]) == lq[:, None, :],
                               axis=-1)
                if tomb is not None:
                    keep = keep & ref.tombstone_mask(tomb, gid)
                d = jnp.where(keep & valid, d, jnp.inf)
        with jax.named_scope("scan.merge"):
            cat_v = jnp.concatenate([run_v, d], axis=1)
            cat_p = jnp.concatenate(
                [run_p, jnp.broadcast_to(pos[None, :], (Q, chunk))], axis=1)
            neg, sel = jax.lax.top_k(-cat_v, kp)
            return (-neg, jnp.take_along_axis(cat_p, sel, axis=1)), None

    if fused:
        # fused scan stage (DESIGN.md §3.9): same chunk schedule, but the
        # per-chunk [Q, chunk] distance buffer lives only inside the
        # kernel (VMEM on the pallas backend) and the running top-k merge
        # is fused in — bit-compatible with the lax.scan below for any
        # (chunk, qtile) decomposition
        vals, pos = fused_segmented_scan(
            q, lq, ax, alw, axn, rows_concat, starts, lens, tomb, scales,
            zeros, kp=kp, lmax=lmax, chunk=chunk, qtile=qtile or 8,
            metric=metric, dtype=dtype, dcols=dcols, backend=backend,
            interpret=interpret)
    else:
        (vals, pos), _ = jax.lax.scan(
            body, init, jnp.arange(0, lmax, chunk, dtype=jnp.int32))
    if rr is not None:
        # ---- stage 2: exact rerank of the compressed-scan shortlist ----
        # re-sort by segment position: shortlist order is (scan-distance,
        # position), but the final tie-break must be (EXACT distance,
        # position) — position-ascending input makes lax.top_k's
        # lower-index preference reproduce exactly that (empties, pos ==
        # lmax, sort to the tail)
        with jax.named_scope("rerank.rows"):
            spos = jnp.sort(pos, axis=1)
            listed = spos < lmax
            sp = jnp.clip(starts[:, None] + spos, 0, max(R - 1, 0))
            sgid = rows_concat[jnp.where(listed, sp, 0)]       # [Q, kp]
            if backend == "pallas":
                # shortlist rows already passed the label/tombstone filter;
                # position-sorted means the first sum(listed) lanes are the
                # live ones, which is exactly the kernel's lens mask
                rside, rlayout = row_sidecar(
                    alw, rrn if metric == "l2" else None)
                d = segmented_gather_distance_pallas(
                    q, lq, rr, rside, sgid,
                    jnp.sum(listed, axis=1).astype(jnp.int32),
                    layout=rlayout, metric=metric, interpret=interpret)
            else:
                xg = rr[sgid]                                  # [Q, kp, D]
                ip = jnp.sum(xg * q[:, None, :], axis=-1)
                d = -ip if metric == "ip" else \
                    qn[:, None] - 2.0 * ip + rrn[sgid]
                d = jnp.where(listed, d, jnp.inf)
        with jax.named_scope("rerank.merge"):
            if kp < k:   # lmax < k: pad the shortlist out to the output width
                d = jnp.pad(d, ((0, 0), (0, k - kp)),
                            constant_values=jnp.inf)
                spos = jnp.pad(spos, ((0, 0), (0, k - kp)),
                               constant_values=lmax)
            neg, sel = jax.lax.top_k(-d, k)
            vals = -neg
            pos = jnp.take_along_axis(spos, sel, axis=1)
    with jax.named_scope("scan.resolve_ids"):
        empty = jnp.isinf(vals)
        pos = jnp.where(empty, lmax, pos)
        vals = jnp.where(empty, jnp.float32(jnp.inf), vals)
        # resolve global ids inside the traced program (empty slot -> the
        # arena-cardinality sentinel), so the executor never touches ids on
        # host and warmup covers the whole path
        gid = jnp.where(empty, ax.shape[0],
                        rows_concat[jnp.clip(starts[:, None] + pos, 0,
                                             max(R - 1, 0))])
    return vals, pos.astype(jnp.int32), gid.astype(jnp.int32)


# Kernel-dispatch-cache telemetry (DESIGN.md §6.3): every dispatch of the
# jit-cached segmented program is counted per launch signature, and cache
# growth (a recompile) is surfaced both as a counter and a gauge so the
# serving zero-retrace invariant is observable, not just pinned by tests.
_M_DISPATCH = _metrics.counter(
    "eli_segmented_dispatches_total",
    "segmented_topk program dispatches by launch signature",
    ("backend", "dtype", "bucket"),
)
_M_TRACES = _metrics.counter(
    "eli_segmented_traces_total",
    "new _segmented_topk programs compiled (jit cache growth)",
)
_M_CACHE = _metrics.gauge(
    "eli_segmented_cache_size",
    "resident _segmented_topk jit cache entries",
)


def segmented_topk(q, lq, ax, alw, axn, rows_concat, starts, lens, *, k: int,
                   lmax: int, metric: str = "l2", backend: str = "ref",
                   chunk: int | None = None, tomb=None, dtype: str = "f32",
                   scales=None, zeros=None, rerank=None, rerank_norms=None,
                   kprime: int | None = None, fused=False,
                   qtile: int | None = None):
    """Single-dispatch segmented arena search (DESIGN.md §3).

    One traced program per (k, Q-bucket, lmax, metric, backend) serves every
    routed group whose candidate segment fits in ``lmax`` — the batched
    executor's arena hot path.  ``backend="ref"`` gathers with ``jnp.take``
    (XLA-fused, the CPU/CI configuration); ``backend="pallas"`` uses the
    per-row DMA gather kernels (compiled on TPU, interpreted elsewhere).

    Returns (vals [Q, k] asc, pos [Q, k] int32 positions RELATIVE to each
    query's segment (pos == ``lmax`` ⇒ empty slot), gid [Q, k] int32
    GLOBAL arena row ids (gid == N ⇒ empty slot)).  Views consume ``pos``
    (their protocol speaks local ids); the batched executor consumes
    ``gid`` directly — no host-side remap exists anywhere on the path.

    ``tomb``: optional packed tombstone bitmap (streaming engine only; the
    static engine passes ``None`` and traces the exact pre-mutation
    program).

    Tiered precision (DESIGN.md §3.8): ``dtype`` + the arena's tier
    operands select the scan representation (``scales``/``zeros`` for
    int8), and ``rerank``/``rerank_norms`` (exact f32 rows + eager norms)
    turn the program two-level — compressed scan to a ``kprime`` (default
    4k) shortlist, exact in-program rerank.  ``dtype="f32"`` with no tier
    operands is byte-for-byte the pre-tier program.

    ``fused`` (DESIGN.md §3.9): ``True`` / ``False`` / ``"auto"`` selects
    the fused scan stage (``kernels/fused_scan.py``) — same results bit
    for bit, but the per-chunk distance buffer never leaves the kernel.
    With ``chunk``/``qtile`` unset, tile sizes come from the roofline
    model (``launch/roofline.py::fused_scan_tiles``), which is
    deterministic per (D, lmax, dtype, Q-bucket, backend, device kind):
    warmup and serving resolve identical tiles, so the fused path adds no
    post-warmup cache keys.  An explicit ``chunk`` always wins (the
    parity tests sweep it).
    """
    dcols = None
    if backend == "pallas":
        if dtype == "int8":
            dcols = ax.shape[1]      # mask lane padding inside the kernel
        ax = _pad_axis(ax, 1, 128)
        q = _pad_axis(q, 1, 128)
        if rerank is not None:
            rerank = _pad_axis(rerank, 1, 128)
    fused = resolve_fused(fused, backend=backend)
    if fused and chunk is None:
        from ..launch import roofline  # lazy: launch/ is orchestration-side
        tc = roofline.fused_scan_tiles(ax.shape[1], lmax, dtype, q.shape[0],
                                       backend=backend,
                                       label_words=alw.shape[1])
        chunk, qtile = tc.rows_per_chunk, qtile or tc.queries_per_tile
        while lmax % chunk:  # non-pow2 lmax (direct callers): degrade
            chunk //= 2
    if not fused:
        qtile = None  # not a knob of the unfused program: one cache key
    before = _segmented_topk._cache_size() if _metrics.enabled() else None
    out = _segmented_topk(
        jnp.asarray(q, jnp.float32), jnp.asarray(lq, jnp.int32),
        ax, alw, axn, rows_concat,
        jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32),
        tomb, scales, zeros, rerank, rerank_norms,
        k=k, lmax=lmax, chunk=chunk or min(SEG_CHUNK, lmax), metric=metric,
        backend=backend, interpret=default_interpret(), dtype=dtype,
        kprime=kprime, dcols=dcols, fused=fused, qtile=qtile)
    if before is not None:
        # tracing (if any) happened synchronously during the call above,
        # so the cache-size delta is already visible here
        after = _segmented_topk._cache_size()
        _M_DISPATCH.labels(backend, dtype, q.shape[0]).inc()
        if after > before:
            _M_TRACES.inc(after - before)
        _M_CACHE.set(after)
    return out


def delta_topk(q, lq, dx, dlw, dxn, tomb, count: int, *, k: int,
               metric: str = "l2", backend: str = "ref",
               chunk: int | None = None, dtype: str = "f32",
               scales=None, zeros=None, rerank=None, rerank_norms=None,
               kprime: int | None = None, fused=False,
               qtile: int | None = None):
    """Brute-force label-filtered top-k over the streaming delta arena
    (DESIGN.md §3.6) — one traced program per (k, Q-bucket, capacity-tier).

    Implemented as the SAME segmented program as the base scan, over an
    identity row table covering the delta's full capacity tier, with every
    query's segment being ``[0, count)`` (the append cursor arrives as a
    traced [Q] length vector, so inserts never retrace) and the delta's own
    tombstone bitmap fused into the filter.  Sharing the program is what
    makes the base+delta merge bit-exact: the inner product is the same
    multiply + minor-axis reduce, so a row scores identically whether it
    lives in the delta or (after compaction / from-scratch rebuild) in the
    base arena.

    Returns (vals [Q, k] asc, slot [Q, k] int32 delta slots; slot ==
    capacity ⇒ empty).  The caller adds the base cardinality to turn slots
    into global stream ids (``merge_topk`` does this in-program).
    """
    cap = dx.shape[0]
    Q = q.shape[0]
    ident = jnp.arange(cap, dtype=jnp.int32)
    starts = jnp.zeros(Q, jnp.int32)
    lens = jnp.full((Q,), min(count, cap), jnp.int32)
    vals, pos, _ = segmented_topk(q, lq, dx, dlw, dxn, ident, starts, lens,
                                  k=k, lmax=cap, metric=metric,
                                  backend=backend, chunk=chunk, tomb=tomb,
                                  dtype=dtype, scales=scales, zeros=zeros,
                                  rerank=rerank, rerank_norms=rerank_norms,
                                  kprime=kprime, fused=fused, qtile=qtile)
    return vals, pos


@jax.jit
def scatter_topk_rows(buf_v, buf_i, idx, vals, ids):
    """Write a tier's [bucket, k] top-k rows into the query-aligned
    [Q-bucket, k] assembly buffers at ``idx`` (out-of-bounds lanes — the
    tier's zero-pad rows — are dropped).  One jitted call per tier: the
    eager ``.at[].set`` pair costs ~ms of host dispatch per call, which
    dominated the streaming executor's small-op tail."""
    return (buf_v.at[idx].set(vals, mode="drop"),
            buf_i.at[idx].set(ids, mode="drop"))


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_topk(bv, bi, dv, dslot, base_offset, sentinel, *, k):
    """In-program base+delta top-k merge preserving the deterministic
    (distance, global-id) tie-break (DESIGN.md §3.6).

    ``bv``/``bi`` [Q, k]: base results — GLOBAL ids ascending within equal
    distances (segments list arena rows in ascending order).  ``dv`` /
    ``dslot`` [Q, k]: delta results by slot, ids ``base_offset + slot``.
    Base rows always carry smaller global ids than delta rows, and
    ``lax.top_k`` breaks value ties toward the lower concatenation index,
    so concatenating [base, delta] yields exactly the (distance, id)
    lexicographic top-k a rebuilt-from-scratch engine computes over the
    union.  Empty slots resolve to ``sentinel`` (the stream cardinality,
    traced so inserts don't retrace) with +inf distance.
    """
    with jax.named_scope("delta.merge"):
        cat_v = jnp.concatenate([bv, dv], axis=1)
        cat_i = jnp.concatenate([bi, base_offset + dslot], axis=1)
        neg, sel = jax.lax.top_k(-cat_v, k)
        vals = -neg
        ids = jnp.take_along_axis(cat_i, sel, axis=1)
        empty = jnp.isinf(vals)
        ids = jnp.where(empty, sentinel, ids)
        vals = jnp.where(empty, jnp.float32(jnp.inf), vals)
    return vals, ids.astype(jnp.int32)


def merge_topk(base_vals, base_gids, delta_vals, delta_slots,
               base_offset: int, sentinel: int, *, k: int):
    """Jit-cached per-(k, Q-bucket) wrapper around :func:`_merge_topk`;
    ``base_offset``/``sentinel`` are passed as traced scalars so mutation
    counters never add dispatch keys."""
    return _merge_topk(base_vals, base_gids, delta_vals, delta_slots,
                       jnp.int32(base_offset), jnp.int32(sentinel), k=k)


def gather_distance(q_row, x, ids, *, metric: str = "l2",
                    backend: str = "pallas") -> jnp.ndarray:
    """[D], [N, D], [B] -> [B] f32; ids < 0 -> +inf (padding)."""
    if backend == "ref":
        return ref.gather_distance(q_row, x, ids, metric)
    xp = _pad_axis(x, 1, 128)
    qp = _pad_axis(q_row[None, :], 1, 128)[0]
    return gather_distance_pallas(qp, xp, jnp.asarray(ids, jnp.int32),
                                  metric=metric, interpret=default_interpret())


__all__ = [
    "LABEL_WORDS",
    "SEG_CHUNK",
    "default_interpret",
    "delta_topk",
    "filtered_topk",
    "gather_distance",
    "masked_distance",
    "masked_topk_tail",
    "merge_topk",
    "prepare_label_words",
    "scatter_topk_rows",
    "segmented_topk",
]


def flash_decode(q, k_cache, v_cache, lengths, *, block_s: int = 512,
                 interpret: bool = True):
    """Padded/jit wrapper for the flash-decoding kernel (kernels/flash_decode).

    Pads the cache sequence dim to a block multiple (masked via lengths) and
    dispatches.  On real TPU pass interpret=False.
    """
    import jax.numpy as jnp

    from .flash_decode import flash_decode_pallas

    S = k_cache.shape[1]
    bs = min(block_s, max(128, 1 << (S - 1).bit_length())) if S < block_s         else block_s
    pad = (-S) % bs
    if pad:
        widths = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    return flash_decode_pallas(q, k_cache, v_cache,
                               lengths.astype(jnp.int32),
                               block_s=bs, interpret=interpret)
