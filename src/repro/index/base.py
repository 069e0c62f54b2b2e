"""VectorIndex protocol — the paper's "modular index" abstraction.

ELI is index-agnostic (paper Table 1, "Index Flexibility"): any index that
supports incremental filtered top-k search can serve as the physical index
behind a selected label group.  Backends register themselves in
``INDEX_REGISTRY`` so the engine, baselines, and benchmarks select them by
name.

Contract:
  * ``build(vectors, label_words, metric, **params)`` — vectors are the
    *selected subset* rows (float32 [n, d]); label_words the matching int32
    [n, W] device-layout masks (needed because a shared index holds entries
    whose label sets do NOT all contain a given query's labels).
  * ``search(queries, query_label_words, k)`` — PostFiltering top-k within
    the index: only rows whose label set contains the query's pass; returns
    (dists [Q, k] f32 asc, ids [Q, k] int32 LOCAL row ids; id == n ⇒ empty
    slot).  Must keep searching (k+1 semantics) until k passing rows are
    accumulated or the index is exhausted — Lemma 3.2's cost model.
  * ``search_padded(queries, query_label_words, k)`` — the batched
    executor's hot path (``LabelHybridEngine.search_batched``).  Same
    semantics as ``search`` with a **static-shape** calling convention:

      - ``queries``/``query_label_words`` arrive padded to a power-of-two
        *bucket* (the executor zero-pads each routed group and slices the
        pad rows off afterwards — each row's filtered top-k is independent
        of its batch neighbors, so padding cannot perturb real rows);
      - the implementation must trace/compile **once per (index, k,
        bucket)** and reuse the compiled executable for every later batch
        that lands in the same bucket — no per-call retracing, no
        data-dependent output shapes;
      - incremental (k+1) continuation is preserved *inside* the traced
        program (e.g. IVF expresses the probe-doubling waves of Lemma 3.2
        as static wave boundaries; the graph backend runs its beam search
        as a fixed-shape ``lax.while_loop``);
      - returns device arrays [bucket, k]; empty slots carry
        (dist == +inf, id == n) exactly like ``search``.

    **Tombstones** (optional keyword ``tomb``, DESIGN.md §3.6): a packed
    uint8 bitmap over the index's *storage rows* — its local row ids
    [0, num_vectors) for a materialized index, the shared arena's global
    rows for an arena view — little bit order
    (:func:`pack_tombstones`); a set bit excludes the row from the
    *result* exactly as if it failed the label containment filter, and
    the incremental (k+1) continuation must widen over it (a tombstoned
    row never counts toward the k accumulated passing rows, so e.g. a
    fully-tombstoned IVF probe wave keeps doubling and still terminates
    at exhaustion).  Tombstones must not perturb surviving rows: every
    returned (dist, id) is bit-identical to the same search over an
    index whose tombstoned rows simply never pass the filter — the
    lazy-delete contract `core.stream.StreamingEngine` relies on.
    Structural traversal MAY still visit tombstoned rows (the graph
    backend deliberately keeps them navigable for connectivity).
    ``tomb=None`` must trace the exact tombstone-free program.  Backends
    implementing this natively set ``supports_tombstones = True``;
    :func:`fallback_search_padded` rejects ``tomb`` so the streaming
    engine folds deletes for backends without the capability.

    Per-instance dispatch tables MUST be keyed by (k, bucket) *within the
    instance* (see :func:`bucket_cache`) so two indexes — or two engines
    with different k living in one process — never cross-contaminate
    compiled-function caches; the shared XLA executable cache underneath
    is keyed on shapes + static arguments and is safe to share.

    Backends registered without a native implementation get
    :func:`fallback_search_padded` (correct, but re-dispatches through
    plain ``search`` and inherits its tracing behavior).
  * ``num_vectors`` — the paper's cost measure (space ∝ #vectors, degree
    bounded by a constant for graphs).
  * ``build_view(arena, rows_concat, start, length, *, metric, **params)``
    — OPTIONAL classmethod capability (DESIGN.md §3): an **arena-native**
    backend materializes a selected index as a *view* over the engine's
    shared :class:`Arena` — an ``(start, length)`` segment of the engine's
    concatenated row-id table — instead of copying its closure's vectors.
    Views satisfy the full ``VectorIndex`` protocol (their ``search`` /
    ``search_padded`` return LOCAL ids exactly like a materialized index)
    but own no vector storage: ``nbytes == 0``, the arena and the segment
    table are counted once at the engine.  Backends without ``build_view``
    keep private storage and the engine falls back to ``build`` on the
    copied rows — the paper's index-flexibility contract is unchanged.

Global-id contract (the executor's sentinel/dtype rules) lives here too:
row ids are int32, the empty-slot sentinel is the dataset cardinality
``n`` itself, and therefore ``n`` must be representable as int32 — see
:func:`check_global_id_contract` / :func:`as_row_ids`, the single home of
that rule (engine, benchmarks, and backends all import it).

Streaming storage (DESIGN.md §3.6) also lives here: the :class:`Arena`
carries a packed tombstone bitmap + mutation ``version``, and
:class:`DeltaArena` is the fixed-capacity append buffer that absorbs
inserts without touching the CSR segment table — both consumed by
``core.stream.StreamingEngine``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Protocol

import numpy as np

ROW_ID_DTYPE = np.int32


class CapacityError(RuntimeError):
    """An insert would push the :class:`DeltaArena` past its maximum
    capacity tier (``max_capacity``).  Typed so callers — the serving
    runtime above all — can surface "corpus full, compact or shard"
    as a result instead of a crash (ISSUE 8 satellite); the raising
    paths are all functional, so the engine state is unchanged."""


def check_global_id_contract(n: int) -> int:
    """Assert the sentinel/dtype contract: ids AND the empty sentinel ``n``
    must fit int32 (the device id dtype).  Returns ``n`` for chaining."""
    if not 0 <= n < np.iinfo(ROW_ID_DTYPE).max:
        raise OverflowError(
            f"dataset cardinality {n} breaks the int32 global-id contract "
            f"(the empty-slot sentinel is n itself and must be "
            f"representable); shard the dataset or widen ROW_ID_DTYPE")
    return n


def as_row_ids(rows: np.ndarray, n: int) -> np.ndarray:
    """Coerce an arena row-id array to the contract dtype, checking range.

    The pre-arena engine stored ``rows`` as int64 and downcast search
    results with a bare ``astype(np.int32)`` — a silent overflow for
    n ≥ 2^31.  Every row table now passes through here instead."""
    check_global_id_contract(n)
    rows = np.ascontiguousarray(rows)
    if rows.size and (rows.min() < 0 or rows.max() >= n):
        raise ValueError(f"row ids outside [0, {n})")
    return rows.astype(ROW_ID_DTYPE, copy=False)


def tombstone_bytes(n_rows: int) -> int:
    """Packed-bitmap size for ``n_rows`` tombstone bits (little bit order:
    row r lives in bit ``r & 7`` of byte ``r >> 3`` — the layout the
    segmented kernel's in-program mask gather assumes, and what
    ``np.packbits(..., bitorder="little")`` produces)."""
    return max(1, -(-n_rows // 8))


def pack_tombstones(dead: np.ndarray, n_rows: int | None = None) -> np.ndarray:
    """Host bool mask (1 = tombstoned) -> packed uint8 bitmap, padded to
    ``tombstone_bytes(n_rows)`` so the device array's shape — and therefore
    the traced search program — never changes across delete batches."""
    n_rows = len(dead) if n_rows is None else n_rows
    bits = np.zeros(8 * tombstone_bytes(n_rows), dtype=bool)
    bits[:len(dead)] = dead
    return np.packbits(bits, bitorder="little")


# ---------------------------------------------------------------------------
# Tiered-precision storage (DESIGN.md §3.8)
# ---------------------------------------------------------------------------

STORAGE_DTYPES = ("f32", "fp16", "int8")


def parse_storage(spec: str) -> tuple[str, bool]:
    """``storage=`` spec string -> (scan-tier dtype, has f32 rerank tier).

    Accepted: ``"f32"`` (today's single-level path, byte-for-byte),
    ``"fp16"`` / ``"int8"`` (compressed scan tier, distances computed on
    dequantized codes), ``"fp16+rerank"`` / ``"int8+rerank"`` (compressed
    shortlist scan to k' candidates, then in-program exact rerank against
    a retained f32 tier).  ``"f32+rerank"`` is rejected — reranking f32
    against itself is the identity and would only double storage."""
    dtype, plus, tail = spec.partition("+")
    rerank = plus == "+"
    if dtype not in STORAGE_DTYPES or (rerank and tail != "rerank") \
            or (not rerank and tail):
        raise ValueError(
            f"unknown storage spec {spec!r}; expected one of "
            f"{STORAGE_DTYPES} optionally suffixed '+rerank'")
    if rerank and dtype == "f32":
        raise ValueError("storage 'f32+rerank' is redundant: the f32 scan "
                         "tier already computes exact distances")
    return dtype, rerank


def quantize_int8(x: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row asymmetric uint8 scalar quantizer (host-side, deterministic).

    ``x`` [M, D] f32 -> (codes [M, D] u8, scale [M] f32, zero [M] f32) with
    ``code = rint((x - zero) / scale)`` clipped to [0, 255], ``zero = row
    min``, ``scale = (row max - row min) / 255`` (1.0 on zero-range rows,
    whose codes are all 0 so the dequant ``zero + scale·code`` reproduces
    them EXACTLY).  Quantization always runs on the host in numpy — the
    same rows produce the same codes whether they arrive via
    ``Arena.from_host`` or a ``DeltaArena`` append, which is what keeps the
    streaming rebuilt-from-scratch parity across compactions
    (DESIGN.md §3.8)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    m = x.shape[0]
    if m == 0:
        return (np.zeros(x.shape, np.uint8), np.ones(0, np.float32),
                np.zeros(0, np.float32))
    lo = x.min(axis=1).astype(np.float32)
    hi = x.max(axis=1).astype(np.float32)
    scale = np.where(hi > lo, (hi - lo) / np.float32(255.0),
                     np.float32(1.0)).astype(np.float32)
    codes = np.clip(np.rint((x - lo[:, None]) / scale[:, None]),
                    0, 255).astype(np.uint8)
    return codes, scale, lo


def dequantize_int8(codes: np.ndarray, scale: np.ndarray,
                    zero: np.ndarray) -> np.ndarray:
    """Numpy dequant ``zero + scale·code`` — elementwise f32 mul+add, so
    bitwise identical to the in-kernel dequantization (both are single
    IEEE operations per element; no accumulation order is involved)."""
    return (zero[:, None]
            + scale[:, None] * codes.astype(np.float32)).astype(np.float32)


def _encode_tier(vectors: np.ndarray, dtype: str):
    """Host rows -> (device codes, device scales|None, device zeros|None,
    device norms).  Norms are the squared norms OF THE DEQUANTIZED values,
    computed with the exact eager ``jnp.sum(xd * xd, axis=1)`` dispatch of
    ``Arena.from_host`` — the scan program's l2 form gathers them, so they
    must match what the in-kernel dequant + reduce would produce, and must
    be identical between a from-scratch upload and a delta append
    (the §3.6 eager-norm rule extended per tier, DESIGN.md §3.8)."""
    import jax.numpy as jnp

    x = np.ascontiguousarray(vectors, dtype=np.float32)
    if dtype == "f32":
        xd = jnp.asarray(x)
        return xd, None, None, jnp.sum(xd * xd, axis=1)
    if dtype == "fp16":
        codes = jnp.asarray(x.astype(np.float16))
        xd = codes.astype(jnp.float32)
        return codes, None, None, jnp.sum(xd * xd, axis=1)
    if dtype == "int8":
        codes_h, scale_h, zero_h = quantize_int8(x)
        codes = jnp.asarray(codes_h)
        scales = jnp.asarray(scale_h)
        zeros = jnp.asarray(zero_h)
        xd = zeros[:, None] + scales[:, None] * codes.astype(jnp.float32)
        return codes, scales, zeros, jnp.sum(xd * xd, axis=1)
    raise ValueError(f"unknown storage dtype {dtype!r}")


@dataclasses.dataclass(frozen=True)
class Arena:
    """Device-resident shared index storage (DESIGN.md §3).

    The dataset's vectors and label words are uploaded ONCE; every selected
    index references them through a row-id segment instead of holding a
    copy, so engine device memory is N·D·4 + N·W·4 (+ N·4 norms) + Σ|I|·4
    bytes instead of Σ|I|·(D+W)·4.  ``norms`` are the precomputed squared
    row norms consumed by the l2 distance form ``qn - 2·ip + xn`` — gathered
    per candidate, bit-identical to recomputing from the gathered row.

    Streaming mutations (DESIGN.md §3.6): ``tombstones`` is a packed
    ⌈N/8⌉-byte bitmap (1 = deleted row) that the segmented search program
    fuses into its label filter — a deleted row simply stops passing, with
    no change to the segment table or to any dispatch key.  ``version``
    grows monotonically with every tombstone write and every compaction, so
    snapshots/caches can detect staleness.  Both updates are functional
    (:meth:`with_tombstones` returns a new Arena sharing the vector
    storage); the un-mutated static engine keeps version 0 and an all-zero
    bitmap, whose mask is the identity.

    Tiered precision (DESIGN.md §3.8): ``dtype`` selects the SCAN tier's
    storage — ``"f32"`` keeps ``vectors`` as today's f32 rows (byte
    identical programs), ``"fp16"`` stores half-precision rows, ``"int8"``
    stores per-row scalar-quantized uint8 codes with ``scales``/``zeros``
    (dequant = zero + scale·code).  ``norms`` are always the squared norms
    of the DEQUANTIZED scan-tier values — what the l2 scan gathers.  An
    optional ``rerank`` tier keeps the exact f32 rows (+ their
    ``rerank_norms``) for the in-program shortlist rerank; the CSR segment
    table, sentinel/dtype contract, and tombstone bitmap are tier-blind.
    """
    vectors: object        # jnp [N, D]: f32 | f16 | u8 codes (see dtype)
    label_words: object    # jnp [N, W] i32
    norms: object          # jnp [N] f32 (of the dequantized scan tier)
    tombstones: object = None   # jnp [⌈N/8⌉] u8; bit set ⇒ row deleted
    version: int = 0            # bumps on every mutation / compaction
    dtype: str = "f32"          # scan-tier storage: f32 | fp16 | int8
    scales: object = None       # jnp [N] f32 (int8 only)
    zeros: object = None        # jnp [N] f32 (int8 only)
    rerank: object = None       # jnp [N, D] f32 exact rows (rerank tier)
    rerank_norms: object = None  # jnp [N] f32 (rerank tier)

    @classmethod
    def from_host(cls, vectors: np.ndarray, label_words: np.ndarray,
                  storage: str = "f32") -> "Arena":
        import jax.numpy as jnp
        n = check_global_id_contract(vectors.shape[0])
        dtype, has_rerank = parse_storage(storage)
        lw = jnp.asarray(np.ascontiguousarray(label_words, dtype=np.int32))
        codes, scales, zeros, norms = _encode_tier(vectors, dtype)
        rr = rrn = None
        if has_rerank:
            rr = jnp.asarray(np.ascontiguousarray(vectors, dtype=np.float32))
            rrn = jnp.sum(rr * rr, axis=1)
        return cls(vectors=codes, label_words=lw, norms=norms,
                   tombstones=jnp.zeros(tombstone_bytes(n), jnp.uint8),
                   dtype=dtype, scales=scales, zeros=zeros,
                   rerank=rr, rerank_norms=rrn)

    @property
    def storage(self) -> str:
        """The ``storage=`` spec string this arena was built with."""
        return self.dtype + ("+rerank" if self.rerank is not None else "")

    def tier_kwargs(self) -> dict:
        """The tier operands of ``kernels.ops.segmented_topk`` (and
        ``delta_topk``) — the one place the arena's storage layout is
        translated into kernel arguments."""
        return dict(dtype=self.dtype, scales=self.scales, zeros=self.zeros,
                    rerank=self.rerank, rerank_norms=self.rerank_norms)

    @property
    def tier_nbytes(self) -> dict:
        """Per-tier device byte split (satellite 1): codes (the scan-tier
        vectors), labels, norms, scales (+zeros), rerank (+its norms),
        tombstone.  ``nbytes`` is exactly the sum of these components."""
        return {
            "codes": int(self.vectors.nbytes),
            "labels": int(self.label_words.nbytes),
            "norms": int(self.norms.nbytes),
            "scales": (int(self.scales.nbytes + self.zeros.nbytes)
                       if self.scales is not None else 0),
            "rerank": (int(self.rerank.nbytes + self.rerank_norms.nbytes)
                       if self.rerank is not None else 0),
            "tombstone": (int(self.tombstones.nbytes)
                          if self.tombstones is not None else 0),
        }

    def with_tombstones(self, dead: np.ndarray) -> "Arena":
        """New Arena (shared vector storage) whose tombstone bitmap marks
        the host bool mask ``dead``; bumps ``version``."""
        import jax.numpy as jnp
        packed = pack_tombstones(np.asarray(dead, dtype=bool), self.n)
        return dataclasses.replace(self, tombstones=jnp.asarray(packed),
                                   version=self.version + 1)

    def padded(self, rows: int) -> "Arena":
        """This arena grown to ``rows`` rows (device-side; never shrunk).
        Pad rows are what :meth:`from_host` makes of zero rows (codes 0,
        int8 scale 1, zero 0, norm 0, no labels, not tombstoned), and no
        scan addresses them: a segment lists real rows only, and the
        in-place read masks positions past its segment's length."""
        import jax.numpy as jnp
        extra = rows - self.n
        if extra <= 0:
            return self

        def pad(buf, value=0):
            if buf is None:
                return None
            return jnp.concatenate(
                [buf, jnp.full((extra,) + buf.shape[1:], value, buf.dtype)])

        tombs = jnp.zeros(tombstone_bytes(rows), jnp.uint8)
        if self.tombstones is not None:
            tombs = tombs.at[:self.tombstones.shape[0]].set(self.tombstones)
        return dataclasses.replace(
            self, vectors=pad(self.vectors),
            label_words=pad(self.label_words), norms=pad(self.norms),
            tombstones=tombs,
            scales=pad(self.scales, 1.0), zeros=pad(self.zeros),
            rerank=pad(self.rerank), rerank_norms=pad(self.rerank_norms))

    @property
    def n(self) -> int:
        """Rows held, pad rows of a padded arena included."""
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def nbytes(self) -> int:
        return sum(self.tier_nbytes.values())


MIN_DELTA_CAPACITY = 256


@dataclasses.dataclass(frozen=True)
class DeltaArena:
    """Fixed-capacity device append buffer for streaming inserts
    (DESIGN.md §3.6).

    Inserts land here — vectors, label words, and precomputed squared norms
    at the append cursor — WITHOUT touching the base arena or the CSR
    segment table, so a mutation never invalidates a traced base program.
    Capacity moves through power-of-two tiers: the brute-force delta scan
    (``kernels.ops.delta_topk``) is traced once per (k, Q-bucket,
    capacity-tier) and masks ``slot >= count`` lanes with the cursor, so
    appends never retrace; only a tier change (rare, growth doubles) does.

    Deletes of delta rows set bits in the delta's own packed tombstone
    bitmap (same layout as :class:`Arena`'s).  All updates are functional —
    the owning :class:`~repro.core.stream.StreamingEngine` holds the
    current instance.  Norms are computed by the same per-row
    multiply+minor-axis-reduce as ``Arena.from_host``, which the merge's
    ULP-parity contract depends on (DESIGN.md §3.6).

    Tiered precision (DESIGN.md §3.8): same ``dtype``/``scales``/``zeros``/
    ``rerank`` layout as :class:`Arena`.  Quantized appends quantize
    EAGERLY on the host (the deterministic :func:`quantize_int8`) and
    compute norms from the dequantized values with the same eager dispatch
    — so a compaction that re-quantizes the host mirror produces the exact
    codes the delta scan already served (the §3.6 parity rule per tier).
    """
    vectors: object       # jnp [cap, D]: f32 | f16 | u8 codes (see dtype)
    label_words: object   # jnp [cap, W] i32
    norms: object         # jnp [cap] f32 (of the dequantized scan tier)
    tombstones: object    # jnp [⌈cap/8⌉] u8; bit set ⇒ slot deleted
    count: int = 0        # append cursor: slots [0, count) hold rows
    dtype: str = "f32"          # scan-tier storage: f32 | fp16 | int8
    scales: object = None       # jnp [cap] f32 (int8 only)
    zeros: object = None        # jnp [cap] f32 (int8 only)
    rerank: object = None       # jnp [cap, D] f32 exact rows (rerank tier)
    rerank_norms: object = None  # jnp [cap] f32 (rerank tier)
    max_capacity: int | None = None  # growth ceiling; exceeding raises

    @classmethod
    def empty(cls, dim: int, words: int,
              capacity: int = MIN_DELTA_CAPACITY,
              storage: str = "f32",
              max_capacity: int | None = None) -> "DeltaArena":
        import jax.numpy as jnp
        cap = pow2_bucket(capacity)
        if max_capacity is not None:
            max_capacity = pow2_bucket(max_capacity)
            if cap > max_capacity:
                raise CapacityError(
                    f"initial delta capacity {cap} exceeds "
                    f"max_capacity {max_capacity}")
        dtype, has_rerank = parse_storage(storage)
        code_dtype = {"f32": jnp.float32, "fp16": jnp.float16,
                      "int8": jnp.uint8}[dtype]
        return cls(vectors=jnp.zeros((cap, dim), code_dtype),
                   label_words=jnp.zeros((cap, words), jnp.int32),
                   norms=jnp.zeros((cap,), jnp.float32),
                   tombstones=jnp.zeros(tombstone_bytes(cap), jnp.uint8),
                   dtype=dtype,
                   scales=(jnp.ones((cap,), jnp.float32)
                           if dtype == "int8" else None),
                   zeros=(jnp.zeros((cap,), jnp.float32)
                          if dtype == "int8" else None),
                   rerank=(jnp.zeros((cap, dim), jnp.float32)
                           if has_rerank else None),
                   rerank_norms=(jnp.zeros((cap,), jnp.float32)
                                 if has_rerank else None),
                   max_capacity=max_capacity)

    @property
    def capacity(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def storage(self) -> str:
        return self.dtype + ("+rerank" if self.rerank is not None else "")

    def tier_kwargs(self) -> dict:
        return dict(dtype=self.dtype, scales=self.scales, zeros=self.zeros,
                    rerank=self.rerank, rerank_norms=self.rerank_norms)

    @property
    def tier_nbytes(self) -> dict:
        return {
            "codes": int(self.vectors.nbytes),
            "labels": int(self.label_words.nbytes),
            "norms": int(self.norms.nbytes),
            "scales": (int(self.scales.nbytes + self.zeros.nbytes)
                       if self.scales is not None else 0),
            "rerank": (int(self.rerank.nbytes + self.rerank_norms.nbytes)
                       if self.rerank is not None else 0),
            "tombstone": int(self.tombstones.nbytes),
        }

    @property
    def nbytes(self) -> int:
        return sum(self.tier_nbytes.values())

    def _buffers(self) -> dict:
        """The cursor-indexed device buffers, as the pytree the generalized
        append/grow operate over (absent tiers simply aren't keys)."""
        bufs = {"vectors": self.vectors, "label_words": self.label_words,
                "norms": self.norms}
        if self.scales is not None:
            bufs["scales"] = self.scales
            bufs["zeros"] = self.zeros
        if self.rerank is not None:
            bufs["rerank"] = self.rerank
            bufs["rerank_norms"] = self.rerank_norms
        return bufs

    def grown(self, min_capacity: int) -> "DeltaArena":
        """Next power-of-two capacity tier holding ``min_capacity`` rows;
        live slots and the tombstone bitmap are copied device-side."""
        import jax.numpy as jnp
        cap = pow2_bucket(min_capacity)
        if cap <= self.capacity:
            return self
        if self.max_capacity is not None and cap > self.max_capacity:
            raise CapacityError(
                f"delta arena cannot grow to {cap} rows "
                f"(max_capacity {self.max_capacity}, {self.count} held); "
                f"flush() to fold the delta into the base arena")
        old = self.capacity

        def widen(buf):
            shape = (cap,) + buf.shape[1:]
            return jnp.zeros(shape, buf.dtype).at[:old].set(buf)

        grown_bufs = {name: widen(buf)
                      for name, buf in self._buffers().items()}
        if "scales" in grown_bufs:
            # untouched slots keep scale 1.0 (masked by count anyway, but a
            # degenerate dequant of an all-zero slot stays finite)
            grown_bufs["scales"] = grown_bufs["scales"].at[old:].set(1.0)
        return dataclasses.replace(
            self,
            tombstones=jnp.zeros(tombstone_bytes(cap), jnp.uint8
                                 ).at[:self.tombstones.shape[0]
                                      ].set(self.tombstones),
            **grown_bufs)

    def appended(self, vectors: np.ndarray,
                 label_words: np.ndarray) -> "DeltaArena":
        """Append ``m`` rows at the cursor (functional).  The batch is
        zero-padded to a power of two so the jitted updater traces once per
        (capacity, batch-tier); pad slots beyond the new cursor are masked
        by ``count`` until a later append overwrites them.  Quantized tiers
        encode the padded batch host-side FIRST (pad rows are constant-zero
        → code 0, scale 1, zero 0 → dequant exactly 0), then compute norms
        eagerly from the dequantized device values — see the class note."""
        import jax.numpy as jnp
        m = vectors.shape[0]
        if m == 0:
            return self
        m_pad = pow2_bucket(m)
        out = self
        if self.count + m_pad > self.capacity:
            out = self.grown(self.count + m_pad)
        rows = np.zeros((m_pad, out.dim), np.float32)
        rows[:m] = vectors
        lws = np.zeros((m_pad, out.label_words.shape[1]), np.int32)
        lws[:m] = label_words
        # norms EAGERLY, with the exact dispatch Arena.from_host uses: the
        # fused-in-jit mul+reduce drifts from the eager one at ULP level,
        # and a folded arena gathers these values — they must be
        # bit-identical to a from-scratch upload (DESIGN.md §3.6/§3.8)
        codes, scales, zeros, norms = _encode_tier(rows, out.dtype)
        parts = {"vectors": codes, "label_words": jnp.asarray(lws),
                 "norms": norms}
        if scales is not None:
            parts["scales"] = scales
            parts["zeros"] = zeros
        if out.rerank is not None:
            rr = jnp.asarray(rows)
            parts["rerank"] = rr
            parts["rerank_norms"] = jnp.sum(rr * rr, axis=1)
        new_bufs = _delta_append(out._buffers(), parts, jnp.int32(out.count))
        return dataclasses.replace(out, count=out.count + m, **new_bufs)

    def with_tombstones(self, dead: np.ndarray) -> "DeltaArena":
        """New DeltaArena whose bitmap marks the host bool mask ``dead``
        (indexed by slot; may be shorter than the capacity)."""
        import jax.numpy as jnp
        packed = pack_tombstones(np.asarray(dead, dtype=bool), self.capacity)
        return dataclasses.replace(self, tombstones=jnp.asarray(packed))


_DELTA_APPEND_JIT = None


def _delta_append(bufs: dict, parts: dict, start):
    """Jitted cursor append over a dict-of-buffers pytree (lazy so this
    module stays importable without touching jax); one trace per
    (capacity, batch-tier, tier-structure) signature.  Norms/codes arrive
    precomputed — see ``DeltaArena.appended``."""
    global _DELTA_APPEND_JIT
    if _DELTA_APPEND_JIT is None:
        import jax

        @jax.jit
        def upd(bufs, parts, start):
            def one(buf, part):
                idx = (start,) + (0,) * (buf.ndim - 1)
                return jax.lax.dynamic_update_slice(buf, part, idx)
            return jax.tree.map(one, bufs, parts)

        _DELTA_APPEND_JIT = upd
    return _DELTA_APPEND_JIT(bufs, parts, start)


class VectorIndex(Protocol):
    num_vectors: int
    dim: int
    metric: str

    def search(self, queries: np.ndarray, query_label_words: np.ndarray,
               k: int) -> tuple[np.ndarray, np.ndarray]:
        ...

    def search_padded(self, queries: np.ndarray,
                      query_label_words: np.ndarray,
                      k: int) -> tuple[np.ndarray, np.ndarray]:
        ...

    @property
    def nbytes(self) -> int:
        ...


def bucket_cache(index) -> dict:
    """The per-instance ``(k, bucket) -> callable`` dispatch table.

    Living on the instance makes index identity part of the cache key by
    construction — the bug class where two indexes (or two engines with
    different k) share one keyed-only-on-bucket table cannot occur.
    Created lazily so third-party ``VectorIndex`` implementations need no
    cooperating ``__init__``.
    """
    cache = getattr(index, "_bucket_fns", None)
    if cache is None:
        cache = {}
        index._bucket_fns = cache
    return cache


def pow2_bucket(g: int, min_bucket: int = 1) -> int:
    """The executor's power-of-two bucket for a group of ``g`` rows."""
    return 1 << (max(g, min_bucket, 1) - 1).bit_length()


def serving_buckets(min_bucket: int, max_batch: int) -> list[int]:
    """The power-of-two Q-bucket ladder a bucket-aware micro-batcher can
    emit: every bucket from the executor's ``min_bucket`` floor up to
    ``pow2_bucket(max_batch)`` inclusive.  The single home of the ladder —
    warmup (``LabelHybridEngine.warmup_serving``) and the serving runtime's
    micro-batcher both enumerate it, so every batch the runtime coalesces
    lands on a pre-traced (k, Q-bucket) program by construction."""
    b = pow2_bucket(min_bucket)
    top = pow2_bucket(max(max_batch, b))
    ladder = []
    while b <= top:
        ladder.append(b)
        b *= 2
    return ladder


def dispatch_padded(search_padded, queries, query_label_words, k,
                    min_bucket: int = 1, **search_params):
    """Zero-pad a raw group to its power-of-two bucket and dispatch.

    Returns the backend's (d, i) — typically still-device arrays of shape
    [bucket, k] — WITHOUT slicing or host synchronization, so the batched
    executor can queue every routed group before blocking once (the
    deferred-sync half of the single-dispatch story; see
    ``LabelHybridEngine.search_batched``).  ``pad_to_bucket`` wraps this
    with the slice-and-materialize convention for direct callers."""
    g = queries.shape[0]
    bucket = pow2_bucket(g, min_bucket)
    qp = np.zeros((bucket, queries.shape[1]), dtype=np.float32)
    qp[:g] = queries
    lp = np.zeros((bucket, query_label_words.shape[1]), dtype=np.int32)
    lp[:g] = query_label_words
    return search_padded(qp, lp, k, **search_params)


def pad_to_bucket(search_padded, queries, query_label_words, k, n,
                  min_bucket: int = 1, **search_params):
    """Dispatch a raw (un-bucketed) batch through ``search_padded`` under
    the executor's power-of-two bucket convention: zero-pad to the bucket
    (≥ ``min_bucket``), search, slice the pad rows off.  The single home
    of the convention — the batched executor and the backends' plain
    ``search`` methods both route through it, so direct callers with
    jittery batch sizes reuse the same traced (index, k, bucket) programs
    instead of compiling one executable per distinct batch size."""
    g = queries.shape[0]
    if g == 0:
        return (np.full((0, k), np.inf, np.float32),
                np.full((0, k), n, np.int32))
    d, i = dispatch_padded(search_padded, queries, query_label_words, k,
                           min_bucket=min_bucket, **search_params)
    return np.asarray(d)[:g], np.asarray(i)[:g]


def fallback_search_padded(self, queries, query_label_words, k,
                           tomb=None, **search_params):
    """Default ``search_padded`` for backends without a native bucketed
    path: delegates to ``search`` on the whole bucket.  Correct under the
    executor's pad-and-slice convention (pad rows are searched and thrown
    away) but only as jit-stable as the backend's ``search`` itself.
    Tombstones are a declared capability (``supports_tombstones``), not
    emulatable through plain ``search`` — callers holding pending deletes
    must fold them for such backends (``core.stream`` does)."""
    if tomb is not None:
        raise TypeError(
            f"backend {getattr(self, 'backend_name', type(self).__name__)!r}"
            f" has no tombstone-aware search_padded; fold deletes before "
            f"searching (see index.base search_padded contract)")
    return self.search(queries, query_label_words, k, **search_params)


INDEX_REGISTRY: dict[str, Callable[..., VectorIndex]] = {}


def register_index(name: str):
    def deco(cls):
        INDEX_REGISTRY[name] = cls
        cls.backend_name = name
        if getattr(cls, "search_padded", None) is None:
            cls.search_padded = fallback_search_padded
        return cls
    return deco


def get_index_builder(name: str):
    try:
        return INDEX_REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown index backend {name!r}; "
                       f"available: {sorted(INDEX_REGISTRY)}") from None
