"""Fused-scan tile selection (DESIGN.md §3.9).

The fused segmented-scan kernel (kernels/fused_scan.py) streams candidate
rows through VMEM in chunks of ``rows_per_chunk`` for ``queries_per_tile``
queries at a time, keeping only the running (distance, position) top-k
resident between chunks.  ``ops.segmented_topk`` asks
:func:`fused_scan_tiles` for both whenever its caller sets no ``chunk``.
The sizes fall out of a small capacity/intensity model:

  * capacity — the chunk buffers, double-buffered, must fit the VMEM the
    compiler allows (``VMEM_BYTES[device_kind]`` · ``VMEM_FRACTION``); the
    lax/CPU fallback uses the same shape of bound against a last-level-
    cache budget (``LLC_BYTES``) so the gathered [qtile, chunk, D] working
    set stays cache-resident;
  * intensity — the scan does ~2·D flops per ``scan_bytes_per_row`` bytes
    of HBM traffic, far below the ridge point (peak FLOP/s over HBM
    bandwidth), so the scan is memory-bound at every storage dtype and the
    model's job is to maximize rows in flight per byte moved, never to
    trade bytes for flops.

The model is *deterministic* per (D, span tier, dtype, Q-bucket, backend,
device kind): warmup and serving resolve the same tiles, so tile selection
adds no jit cache keys post-warmup.  ``autotune_fused_tiles`` is the
measured escape hatch — it overrides the model for the rest of the
process, cached per device kind, and must therefore run BEFORE warmup.
"""
from __future__ import annotations

import dataclasses

# VMEM the TPU compiler lets one kernel allocate, per device kind: the
# limit a compile for a described chip reports when a kernel's scratch is
# larger ("Allocation ... would exceed memory (size=134217728)", jax 0.9.0
# with libtpu 0.0.34, compiled for v5e:2x2).  A Pallas launch on a kind
# missing here is an error, not a default.
VMEM_BYTES = {"TPU v5 lite": 128 * 2**20}
# Pallas interpret mode (off-TPU) runs the kernel with the tiles of this
# chip, so CPU tests exercise the schedule the chip compiles
INTERPRET_DEVICE_KIND = "TPU v5 lite"
VMEM_FRACTION = 0.5         # double-buffering + compiler headroom
LLC_BYTES = 8 * 2**20       # lax fallback: cache-resident working set
MAX_ROWS_IN_FLIGHT = 1024   # pallas: row DMAs issued per step before a wait
LABEL_WORD_BYTES = 4
SIDE_ROW_BYTES = 128 * 4    # one 128-lane i32 sidecar row per candidate

_DTYPE_BYTES = {"f32": 4, "fp16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """One resolved fused-scan tile: the schedule plus the model terms the
    benchmark compares against realized traffic (exp13)."""
    rows_per_chunk: int
    queries_per_tile: int
    bytes_per_row: int      # predicted HBM bytes per scanned candidate row
    intensity: float        # flops/byte of the scan at this dtype
    source: str = "model"   # "model" | "autotuned"


# measured-autotune overrides, keyed per device kind (escape hatch; the
# model answers everything not explicitly autotuned)
_TILE_OVERRIDES: dict[tuple, TileChoice] = {}


def _pow2_floor(x: int) -> int:
    return 1 << (max(1, x).bit_length() - 1)


def scan_bytes_per_row(d: int, dtype: str,
                       label_words: int = 8) -> int:
    """Model HBM traffic per scanned candidate row: codes + label words +
    the gathered norm + the int8 scale/zero sidecar + the row id itself.
    This is the fused path's ideal — the unfused executor additionally
    round-trips the gathered [Q, chunk, D] intermediate."""
    nbytes = _DTYPE_BYTES[dtype] * d + label_words * LABEL_WORD_BYTES + 4 + 4
    if dtype == "int8":
        nbytes += 8          # per-row f32 scale + zero
    return nbytes


def _tile_key(d, lmax, dtype, q_bucket, backend, device_kind):
    return (device_kind, backend, d, lmax, dtype, q_bucket)


def fused_scan_tiles(d: int, lmax: int, dtype: str, q_bucket: int, *,
                     backend: str = "ref", label_words: int = 8,
                     device_kind: str | None = None) -> TileChoice:
    """Pick (rows_per_chunk, queries_per_tile) for one fused-scan launch.

    ``d`` is the operand feature width as the kernel sees it (the pallas
    path passes the 128-lane-padded width), ``lmax`` the power-of-two
    candidate-span tier, ``q_bucket`` the padded query count.  Honors any
    :func:`autotune_fused_tiles` override for this key first.  Every
    returned ``rows_per_chunk`` is a power of two ≤ ``lmax`` (so it divides
    the span) and ``queries_per_tile`` a power of two ≤ ``q_bucket``."""
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"unknown storage dtype {dtype!r}")
    if device_kind is None:
        device_kind = _device_kind(backend)
    key = _tile_key(d, lmax, dtype, q_bucket, backend, device_kind)
    hit = _TILE_OVERRIDES.get(key)
    if hit is not None:
        return hit
    row_bytes = scan_bytes_per_row(d, dtype, label_words)
    intensity = (2.0 * d + 6.0) / row_bytes
    q_bucket = max(1, q_bucket)
    if backend == "pallas":
        if device_kind not in VMEM_BYTES:
            raise ValueError(
                f"no VMEM entry for device kind {device_kind!r}: compile the "
                f"fused scan for it and add the limit to VMEM_BYTES")
        # VMEM-resident chunk buffers per query: the 128-lane i32 sidecar
        # row (labels, norm, int8 codes and scale/zero-point, liveness;
        # kernels/fused_scan.py::row_sidecar), plus the codes row for the
        # tiers gathered on their own — double-buffered.
        vrow = SIDE_ROW_BYTES + (0 if dtype == "int8"
                                 else _DTYPE_BYTES[dtype] * d)
        qt = min(_pow2_floor(q_bucket), 8)
        budget = int(VMEM_BYTES[device_kind] * VMEM_FRACTION)
        chunk = _pow2_floor(max(8, budget // (2 * qt * vrow)))
        # every row copy of a grid step is in flight before the first
        # wait; cap how many (a bound on DMA queueing, not on memory)
        chunk = min(chunk, max(8, MAX_ROWS_IN_FLIGHT // qt))
    else:
        # lax fallback: keep the gathered rows + the elementwise product
        # (~2 live [qtile, chunk, D] f32 arrays) inside the cache budget
        qt = min(_pow2_floor(q_bucket), 16)
        chunk = _pow2_floor(max(32, LLC_BYTES // (2 * qt * d * 4)))
    chunk = min(chunk, lmax)
    qt = min(qt, _pow2_floor(q_bucket))
    return TileChoice(rows_per_chunk=max(1, chunk), queries_per_tile=qt,
                      bytes_per_row=row_bytes, intensity=intensity)


def autotune_fused_tiles(d: int, lmax: int, dtype: str, q_bucket: int, *,
                         backend: str = "ref", label_words: int = 8,
                         device_kind: str | None = None,
                         measure=None, candidates=None) -> TileChoice:
    """Measured escape hatch: time ``measure(TileChoice) -> seconds`` over
    ``candidates`` (default: the model's pick plus its power-of-two chunk
    neighbors) and pin the winner for this (device kind, launch) key for
    the rest of the process.  Run BEFORE warmup: an override installed
    after warmup changes the chunk count of the traced program and the
    next dispatch pays a retrace (the zero-new-traces invariant holds per
    tile choice, not across tile changes)."""
    if device_kind is None:
        device_kind = _device_kind(backend)
    base = fused_scan_tiles(d, lmax, dtype, q_bucket, backend=backend,
                            label_words=label_words,
                            device_kind=device_kind)
    if candidates is None:
        chunks = {base.rows_per_chunk}
        for shift in (-2, -1, 1, 2):
            c = (base.rows_per_chunk << shift if shift > 0
                 else base.rows_per_chunk >> -shift)
            if 1 <= c <= lmax:
                chunks.add(c)
        candidates = [dataclasses.replace(base, rows_per_chunk=c,
                                          source="autotuned")
                      for c in sorted(chunks)]
    if measure is None:
        raise ValueError("autotune_fused_tiles needs a measure callback")
    best = min(candidates, key=measure)
    best = dataclasses.replace(best, source="autotuned")
    _TILE_OVERRIDES[_tile_key(d, lmax, dtype, q_bucket, backend,
                              device_kind)] = best
    return best


def _device_kind(backend: str) -> str:
    import jax
    if backend == "pallas" and jax.default_backend() != "tpu":
        return INTERPRET_DEVICE_KIND
    return jax.devices()[0].device_kind
