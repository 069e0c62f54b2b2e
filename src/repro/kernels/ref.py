"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the kernel allclose tests and the fallback
implementation on backends without Pallas support.  Semantics:

  * distances are **squared L2** (metric="l2") or **negative inner product**
    (metric="ip") — both "smaller is closer", so top-k = k smallest.
  * the label filter keeps row i iff ``lq ⊆ lx[i]`` word-wise
    ((lq & lx[i]) == lq for every 32-bit word); filtered-out rows get +inf.
"""
from __future__ import annotations

import jax.numpy as jnp
import jax

FILTERED = jnp.float32(jnp.inf)


def distances(q: jnp.ndarray, x: jnp.ndarray, metric: str = "l2") -> jnp.ndarray:
    """[Q, D] x [N, D] -> [Q, N] distance matrix (f32 accumulate).  Full
    f32 matmul precision: on a TPU the default runs a single bf16 pass,
    and this is the exact reference."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    ip = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -ip
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        xn = jnp.sum(x * x, axis=1, keepdims=True)
        return qn - 2.0 * ip + xn.T
    raise ValueError(f"unknown metric {metric!r}")


def containment_mask(lq_words: jnp.ndarray, lx_words: jnp.ndarray) -> jnp.ndarray:
    """[Q, W] query masks vs [N, W] db masks -> [Q, N] bool (query ⊆ db)."""
    lq = lq_words[:, None, :]        # [Q, 1, W]
    lx = lx_words[None, :, :]        # [1, N, W]
    return jnp.all((lq & lx) == lq, axis=-1)


def masked_distance(q, x, lq_words, lx_words, metric: str = "l2") -> jnp.ndarray:
    """Fused distance + label-containment filter oracle: [Q, N] f32."""
    d = distances(q, x, metric)
    keep = containment_mask(lq_words, lx_words)
    return jnp.where(keep, d, FILTERED)


def filtered_topk(q, x, lq_words, lx_words, k: int, metric: str = "l2",
                  tomb=None):
    """Exact filtered top-k oracle: (vals [Q, k], idxs [Q, k]).

    Ties broken toward the lower index (matches the kernel's deterministic
    iota tie-break).  Rows with fewer than k passing entries pad with
    (+inf, N) — N is an intentionally out-of-range sentinel.

    ``tomb``: optional packed tombstone bitmap [⌈N/8⌉] u8 over the row ids
    (see :func:`tombstone_mask`) — a set bit drops the row exactly like a
    failed label containment, so tombstones compose with PostFiltering
    without touching any surviving distance (the ``search_padded``
    protocol's lazy-delete contract, DESIGN.md §3.6).
    """
    d = masked_distance(q, x, lq_words, lx_words, metric)
    n = x.shape[0]
    if tomb is not None:
        alive = tombstone_mask(tomb, jnp.arange(n, dtype=jnp.int32))
        d = jnp.where(alive[None, :], d, FILTERED)
    if k > n:  # fewer rows than requested: pad the distance matrix
        d = jnp.pad(d, ((0, 0), (0, k - n)), constant_values=jnp.inf)
    # stable lexicographic top-k: sort by (distance, index)
    order = jnp.argsort(d, axis=1, stable=True)[:, :k]
    vals = jnp.take_along_axis(d, order, axis=1)
    idxs = jnp.where(jnp.isinf(vals), n, order)
    vals = jnp.where(jnp.isinf(vals), FILTERED, vals)
    return vals, idxs.astype(jnp.int32)


def tombstone_mask(tomb: jnp.ndarray, gid: jnp.ndarray) -> jnp.ndarray:
    """Gathered per-row liveness from a packed tombstone bitmap.

    ``tomb`` [⌈N/8⌉] uint8 (bit set ⇒ row deleted, little bit order —
    the layout of ``index.base.pack_tombstones``); ``gid`` int32 row ids of
    any shape.  Returns bool, True ⇒ row alive.  This is the "one extra
    AND" the streaming subsystem fuses into the label filter
    (DESIGN.md §3.6): it only ever *removes* rows from the keep mask, so
    every distance value that survives is untouched.
    """
    byte = tomb.astype(jnp.int32)[jnp.clip(gid >> 3, 0, tomb.shape[0] - 1)]
    return ((byte >> (gid & 7)) & 1) == 0


def dequantize_rows(xg, dtype: str, scales_g=None, zeros_g=None):
    """Gathered scan-tier rows -> f32 values the distance math consumes.

    ``xg`` [..., D] (f32 / f16 / u8 codes per ``dtype``); for int8 the
    gathered per-row ``scales_g``/``zeros_g`` [...] broadcast over the
    feature axis: dequant = zero + scale·code — one IEEE mul + add per
    element, so the value is identical whether computed here, eagerly at
    upload time (``index.base._encode_tier``), or inside the Pallas kernel.
    """
    if dtype == "f32":
        return xg
    if dtype == "fp16":
        return xg.astype(jnp.float32)
    if dtype == "int8":
        return (zeros_g[..., None]
                + scales_g[..., None] * xg.astype(jnp.float32))
    raise ValueError(f"unknown storage dtype {dtype!r}")


def np_quantized_distances(q, codes, scale, zero, lq_words, lx_words,
                           metric: str = "l2") -> "np.ndarray":
    """Numpy quantized-scan oracle (DESIGN.md §3.8): float64 distances of
    every query to every DEQUANTIZED int8 row, +inf where the label filter
    fails.  The f32 dequant is bitwise the kernel's (elementwise); the f64
    accumulation defines the reference ordering the compressed-scan
    shortlist is checked against (shortlist membership up to f32-rounding
    boundary ties — tests/test_quantized_arena.py)."""
    import numpy as np

    xd = (zero[:, None].astype(np.float32)
          + scale[:, None].astype(np.float32)
          * codes.astype(np.float32)).astype(np.float64)
    qd = np.asarray(q, np.float64)
    ip = qd @ xd.T
    if metric == "ip":
        d = -ip
    else:
        d = (np.sum(qd * qd, axis=1)[:, None] - 2.0 * ip
             + np.sum(xd * xd, axis=1)[None, :])
    lq = np.asarray(lq_words)[:, None, :]
    lx = np.asarray(lx_words)[None, :, :]
    keep = np.all((lq & lx) == lq, axis=-1)
    return np.where(keep, d, np.inf)


def segmented_filtered_topk(q, lq, ax, alw, axn, rows_concat, starts, lens,
                            k: int, lmax: int, metric: str = "l2",
                            tomb=None, dtype: str = "f32", scales=None,
                            zeros=None, rerank=None, rerank_norms=None,
                            kprime: int | None = None):
    """Segmented arena top-k oracle (DESIGN.md §3): one batch, one program.

    Every query carries its own candidate segment — a ``(start, len)`` span
    of ``rows_concat``, the engine's CSR table of arena row ids.  The oracle
    gathers each query's candidate rows from the shared arena, fuses the
    label filter, and takes a position-stable top-k:

      * ``q`` [Q, D] f32, ``lq`` [Q, W] i32 — queries + label words;
      * ``ax`` [N, D] f32, ``alw`` [N, W] i32, ``axn`` [N] f32 — the arena
        (vectors, label words, precomputed squared row norms);
      * ``rows_concat`` [R] i32 — concatenated per-index arena row ids;
      * ``starts``/``lens`` [Q] i32 — each query's segment; ``lmax`` bounds
        every ``len`` in the batch (the static candidate-span shape).

    Returns (vals [Q, k] asc, pos [Q, k] int32 segment-RELATIVE positions;
    pos == ``lmax`` ⇒ empty slot).  Ties break toward the lower position —
    segments list arena rows in ascending global order, so this reproduces
    the flat sub-index scan's lower-local-id (= lower-global-id) tie-break.

    ``tomb``: optional packed tombstone bitmap [⌈N/8⌉] u8 fused into the
    keep mask (see :func:`tombstone_mask`); ``None`` keeps the static
    (mutation-free) program unchanged.

    Tiered precision (DESIGN.md §3.8): ``dtype``/``scales``/``zeros``
    select the scan tier (distances on :func:`dequantize_rows` values —
    ``"f32"`` is byte-for-byte today's path); with a ``rerank`` tier the
    scan keeps a k' = ``kprime`` (default 4k) shortlist, which is then
    re-sorted by segment position and reranked against the exact f32 rows
    — the unchunked oracle of the two-level ``ops._segmented_topk``.
    """
    Q = q.shape[0]
    R = rows_concat.shape[0]
    kp = k if rerank is None else max(k, min(kprime or 4 * k, lmax))
    pos = jnp.arange(lmax, dtype=jnp.int32)[None, :]          # [1, L]
    valid = pos < lens[:, None]                               # [Q, L]
    p = jnp.clip(starts[:, None] + pos, 0, max(R - 1, 0))
    gid = rows_concat[jnp.where(valid, p, 0)]                 # [Q, L]
    xg = dequantize_rows(ax[gid], dtype,
                         None if scales is None else scales[gid],
                         None if zeros is None else zeros[gid])  # [Q, L, D]
    # multiply + minor-axis reduce (not dot_general): batch-composition
    # independent f32 accumulation — see kernels.ops._segmented_topk
    ip = jnp.sum(xg * q[:, None, :], axis=-1)
    qn = jnp.sum(q * q, axis=1)
    if metric == "ip":
        d = -ip
    else:
        d = qn[:, None] - 2.0 * ip + axn[gid]
    keep = jnp.all((lq[:, None, :] & alw[gid]) == lq[:, None, :], axis=-1)
    if tomb is not None:
        keep = keep & tombstone_mask(tomb, gid)
    d = jnp.where(keep & valid, d, FILTERED)
    if kp > lmax:   # fewer candidates than requested: pad the span
        d = jnp.pad(d, ((0, 0), (0, kp - lmax)), constant_values=jnp.inf)
    neg, sel = jax.lax.top_k(-d, kp)
    vals = -neg
    sel = jnp.where(jnp.isinf(vals), lmax, sel)
    vals = jnp.where(jnp.isinf(vals), FILTERED, vals)
    if rerank is not None:
        # re-sort the shortlist by segment position: lax.top_k breaks
        # value ties toward the lower index, so position order makes the
        # final (exact-distance, position) order identical to the
        # single-level f32 program's whenever the shortlist covers it
        order = jnp.argsort(sel, axis=1, stable=True)
        spos = jnp.take_along_axis(sel, order, axis=1)
        listed = spos < lmax
        sp = jnp.clip(starts[:, None] + spos, 0, max(R - 1, 0))
        sgid = rows_concat[jnp.where(listed, sp, 0)]
        xg = rerank[sgid]                                     # [Q, kp, D]
        ip = jnp.sum(xg * q[:, None, :], axis=-1)
        d = -ip if metric == "ip" else \
            qn[:, None] - 2.0 * ip + rerank_norms[sgid]
        d = jnp.where(listed, d, FILTERED)
        if kp < k:   # lmax < k: pad the shortlist out to k
            d = jnp.pad(d, ((0, 0), (0, k - kp)), constant_values=jnp.inf)
            spos = jnp.pad(spos, ((0, 0), (0, k - kp)), constant_values=lmax)
        neg, rsel = jax.lax.top_k(-d, k)
        vals = -neg
        sel = jnp.take_along_axis(spos, rsel, axis=1)
        sel = jnp.where(jnp.isinf(vals), lmax, sel)
        vals = jnp.where(jnp.isinf(vals), FILTERED, vals)
    return vals, sel.astype(jnp.int32)


def gather_distance(q_row, x, ids, metric: str = "l2") -> jnp.ndarray:
    """Graph-search hot loop oracle: distances from one query to X[ids].

    ``ids`` may contain -1 padding → +inf distance.
    """
    valid = ids >= 0
    rows = x[jnp.clip(ids, 0, x.shape[0] - 1)]
    d = distances(q_row[None, :], rows, metric)[0]
    return jnp.where(valid, d, FILTERED)


def blockwise_topk_merge(vals_blocks, idxs_blocks, k: int):
    """Merge per-block partial top-k: [Q, NB, K] -> (vals [Q, k], idxs [Q, k]).

    Oracle for the two-stage kernel pipeline (block top-k + lax.top_k merge).
    """
    Q = vals_blocks.shape[0]
    flat_v = vals_blocks.reshape(Q, -1)
    flat_i = idxs_blocks.reshape(Q, -1)
    # smaller distance = better -> top_k on negative values
    neg, pos = jax.lax.top_k(-flat_v, k)
    return -neg, jnp.take_along_axis(flat_i, pos, axis=1)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """Oracle for flash_decode: one-token GQA attention vs a length-masked
    KV cache, all in fp32.  q [B,H,Dh]; k/v [B,S,KH,Dh]; lengths [B]."""
    B, H, Dh = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qf = q.astype(jnp.float32).reshape(B, KH, G, Dh)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, kf) / jnp.sqrt(
        jnp.asarray(Dh, jnp.float32))
    valid = (jnp.arange(S)[None, :] < lengths[:, None])      # [B, S]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", w, vf)
    return out.reshape(B, H, Dh).astype(q.dtype)
