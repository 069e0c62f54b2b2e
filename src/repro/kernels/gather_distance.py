"""Pallas TPU kernels: gathered-row distances.

``segmented_gather_distance_pallas`` is the unfused arena scan's gather
(and the rerank stage's): the fused scan's id-window, row-gather and
distance blocks (``fused_scan.py``) with the distances written out.

``gather_distance_pallas``: the graph-backend search loop repeatedly needs
distances from the query to a *scattered* candidate set (the frontier's neighbor lists).  On TPU the
idiomatic pattern is scalar prefetch: the candidate id array arrives in SMEM
ahead of the grid, and each grid step's BlockSpec ``index_map`` reads the id
to DMA exactly that database row HBM→VMEM — a software-pipelined gather, no
host round-trip.

One grid step processes one candidate row (rows are scattered, so a block
cannot span several).  Padding ids (< 0) are clamped to row 0 by the
index_map and masked to +inf by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_scan import (SIDE_LANES, clamp_qtile, dma_id_windows,
                         filtered_distances, gather_rows, id_window,
                         pack_query, pad_id_table)

INF = float("inf")


def _gather_distance_kernel(ids_ref, q_ref, x_ref, out_ref, *, metric: str):
    q = q_ref[...].astype(jnp.float32)          # [1, D]
    xr = x_ref[...].astype(jnp.float32)         # [1, D]
    ip = jnp.sum(q * xr)
    if metric == "ip":
        d = -ip
    else:
        d = jnp.sum((q - xr) ** 2)
    out_ref[0, 0] = d


@functools.partial(jax.jit, static_argnames=("layout", "metric", "dtype",
                                             "interpret", "dcols"))
def segmented_gather_distance_pallas(q, lq, x, side, gids, lens, *,
                                     layout: tuple, metric: str = "l2",
                                     dtype: str = "f32",
                                     interpret: bool,
                                     dcols: int | None = None):
    """Segmented arena gather + fused filtered distance (DESIGN.md §3).

    ``q`` [Q, Dp] f32, ``lq`` [Q, W] i32; ``x`` [N, Dp] arena rows (f32 or
    f16), or None where the codes ride in the sidecar (int8 tier);
    ``side``/``layout`` the per-row sidecar of ``fused_scan.row_sidecar``
    (label words, the norm for l2, the int8 codes and scale/zero-point,
    liveness); ``gids`` [Q, L] int32 arena row ids per query (already
    resolved through the engine's CSR segment table, in range); ``lens``
    [Q] int32 — positions >= len are masked to +inf, as are rows the label
    filter or a tombstone drops.  Returns [Q, L] f32 distances.

    TPU mapping: the same building blocks as the fused scan — a grid over
    (query tile, L chunk) whose step DMAs the tile's id windows from the
    flattened ``gids`` into SMEM, gathers the referenced rows HBM→VMEM with
    per-row async copies, and computes the filtered distances in registers
    — but the chunk's distances go out to HBM instead of into a running
    top-k.
    """
    Q, L = gids.shape
    Dp = q.shape[1]
    qtile = Q if Q <= 8 else clamp_qtile(8, Q)
    chunk = 128 if L % 128 == 0 else L
    win = id_window(chunk)
    ids, _ = pad_id_table(gids.reshape(-1), chunk)
    q = pack_query(q, layout)
    scratch = [pltpu.SMEM((qtile * win,), jnp.int32),
               pltpu.VMEM((qtile, chunk, SIDE_LANES), jnp.int32),
               pltpu.SemaphoreType.DMA]
    operands = [ids, side]
    if x is not None:
        scratch.append(pltpu.VMEM((qtile, chunk, Dp), x.dtype))
        operands.append(x)

    def kernel(lens_sm, q_ref, lq_ref, ids_ref, side_ref, *rest):
        if x is None:
            x_ref, xbuf = None, None
            out_ref, idbuf, sbuf, sem = rest
        else:
            x_ref, out_ref, idbuf, sbuf, sem, xbuf = rest
        ti = pl.program_id(0)
        c0 = pl.program_id(1) * chunk
        offs = dma_id_windows(
            ids_ref, idbuf, sem,
            [(ti * qtile + t) * L + c0 for t in range(qtile)], win)
        lens_t = [lens_sm[ti * qtile + t] for t in range(qtile)]
        gather_rows(idbuf, offs,
                    [jnp.clip(n - c0, 0, chunk) for n in lens_t],
                    side_ref, sbuf, x_ref, xbuf, sem)
        d = filtered_distances(
            q_ref[...], lq_ref[...], None if xbuf is None else xbuf[...],
            sbuf[...], layout, metric=metric, dtype=dtype, dcols=dcols)
        lens_vec = jnp.stack(lens_t)
        pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (qtile, chunk), 1)
        out_ref[...] = jnp.where(pos < lens_vec[:, None], d, INF)

    def im(i, j, lens_ref):
        return (i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q // qtile, L // chunk),
        in_specs=[pl.BlockSpec((qtile, Dp), im),
                  pl.BlockSpec((qtile, lq.shape[1]), im)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
        out_specs=pl.BlockSpec((qtile, chunk), lambda i, j, lens_ref: (i, j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, L), jnp.float32),
        interpret=interpret,
    )(lens.astype(jnp.int32), q, lq, *operands)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_distance_pallas(q_row, x, ids, *, metric: str = "l2",
                           interpret: bool = True):
    """[D], [N, D], [B] int32 -> [B] f32 distances; ids < 0 -> +inf."""
    B = ids.shape[0]
    D = q_row.shape[0]
    clamped = jnp.maximum(ids, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, D), lambda i, ids_ref: (0, 0)),
            pl.BlockSpec((1, D), lambda i, ids_ref: (ids_ref[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, ids_ref: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_distance_kernel, metric=metric),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        interpret=interpret,
    )(clamped, q_row[None, :], x)
    return jnp.where(ids >= 0, out[:, 0], INF)
