"""LabelHybridEngine — the end-to-end ELI runtime.

Pipeline (paper §3-§5):
  1. group the labelled dataset (GroupTable; exact or sampled closure sizes),
  2. run selection — EIS (fixed elastic-factor bound c) or SIS (fixed space
     budget τ, binary search for the best c),
  3. materialize one physical index per selected label-set key over its
     closure S(L) (any registered backend: flat / ivf / graph / distributed),
  4. route each query to its assigned index (max elastic factor) and run a
     PostFiltering top-k inside it; ids come back global.

Storage (DESIGN.md §3): selected indexes are *closures over one dataset*,
so the engine keeps the dataset in a device-resident :class:`Arena`
(vectors + label words uploaded once) and represents every selected index
as a row-id segment of one concatenated CSR table (``rows_concat`` +
per-key offsets), built at selection time.  Arena-native backends (those
with a ``build_view`` capability — flat) materialize zero-copy views;
backends with private storage (ivf's cluster-major reorder, graph's
adjacency, distributed's sharded copy) fall back to ``build`` on the
copied rows, exactly as before.

The engine is the artifact behind every benchmark figure and the serving
integration (repro.serve).  Routing of query label sets *outside* the
selection workload falls back to the smallest selected superset-key index —
the same max-elastic-factor rule, evaluated lazily.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Mapping, Sequence

import numpy as np

from ..index.base import (Arena, as_row_ids, check_global_id_contract,
                          dispatch_padded, fallback_search_padded,
                          get_index_builder, parse_storage, pow2_bucket)
from ..kernels import ops as _kernel_ops
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .eis import EISResult, greedy_eis
from .elastic import min_elastic_factor
from .estimator import sampled_group_table
from .groups import EMPTY_KEY, GroupTable, observed_query_keys
from .labels import (encode_label_set, encode_many, key_contains,
                     key_to_mask, mask_key, masks_to_int32_words)
from .sis import SISResult, sis


# Search-path telemetry (DESIGN.md §6.3).  Everything here is host-side
# bookkeeping gated on the obs enabled flags: with telemetry off the whole
# apparatus is one boolean check per site, and with it on nothing touches
# the device (a span opens a host-side profiler annotation while tracing
# is on) — search bits and the jit caches are untouched either way
# (pinned by tests/test_obs_invariants.py).
_M_QUERIES = _metrics.counter(
    "eli_search_queries_total", "queries served by the batched executor",
    ("backend",),
)
_M_BATCHES = _metrics.counter(
    "eli_search_batches_total", "search_batched calls", ("backend",),
)
_M_LAT = _metrics.histogram(
    "eli_search_latency_seconds",
    "end-to-end search_batched wall time by launch signature",
    ("backend", "bucket", "dtype"),
)
_M_STAGE = _metrics.histogram(
    "eli_search_stage_seconds",
    "search_batched host stages: route, pack, launch, sync",
    ("stage",),
)
_M_EF = _metrics.histogram(
    "eli_elastic_factor_realized",
    "per-query realized elastic factor |S(L_q)|/|I_i| at the routed index",
    ("backend",),
    buckets=(0.05, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0),
)
_M_EF_BOUND = _metrics.gauge(
    "eli_elastic_factor_bound",
    "configured elastic-factor bound c of the live selection",
)
_M_EF_VIOL = _metrics.counter(
    "eli_elastic_bound_violations_total",
    "queries whose realized elastic factor fell below the configured bound",
)
_M_UNSEEN = _metrics.counter(
    "eli_route_unseen_keys_total",
    "queries routed through the fallback path (key outside the workload)",
)
_M_RECOMPILE = _metrics.counter(
    "eli_search_recompiles_total",
    "search batches that grew the _segmented_topk jit cache post-warmup",
)
_M_ENGINE_GAUGE = _metrics.gauge(
    "eli_engine_rows", "engine row accounting", ("state",),
)
_M_ENGINE_BYTES = _metrics.gauge(
    "eli_engine_nbytes", "engine device-memory split", ("component",),
)
_M_SELECTED = _metrics.gauge(
    "eli_selected_indexes", "physical indexes in the live selection",
)
_M_ENTRIES = _metrics.gauge(
    "eli_selection_entries_total", "Σ|I| rows stored across the selection",
)
_M_ACHIEVED = _metrics.gauge(
    "eli_elastic_factor_achieved",
    "min realized elastic factor over the selection workload (stats())",
)
_M_IN_PLACE = _metrics.counter(
    "eli_scan_in_place_queries_total",
    "real queries whose base scan read the arena in place (DESIGN.md §3.10)",
)


class StageClock:
    """Host seconds of one search call's stages, for
    ``eli_search_stage_seconds``: :meth:`lap` charges the time since the
    previous lap (or the clock's start) to a stage.  Stamps are taken only
    while telemetry is on, at the points where the call's program spans
    (``search.route``, ``search.pack``, ``search.launch``,
    ``search.sync``) open and close."""

    __slots__ = ("on", "t0", "t", "seconds")

    def __init__(self, on: bool):
        self.on = on
        self.t0 = self.t = time.perf_counter() if on else 0.0
        self.seconds: dict[str, float] = {}

    def lap(self, stage: str) -> None:
        if self.on:
            t = time.perf_counter()
            self.seconds[stage] = self.seconds.get(stage, 0.0) + t - self.t
            self.t = t


def record_search_telemetry(engine, routed, qmasks, k, n_queries, clock, *,
                            seg_before=None, tier_bucket=None, min_bucket=1,
                            tomb_density=None, backend=None):
    """Per-batch query-path accounting — the single home of the metrics
    + query-card emission shared by ``LabelHybridEngine.search_batched``
    and the streaming executor (``core.stream``).  Called only when
    telemetry is enabled; pure host work.  ``clock`` is the call's
    :class:`StageClock`."""
    t_end = time.perf_counter()
    backend = backend or engine.backend
    arena = getattr(engine, "arena", None)
    dtype = arena.dtype if arena is not None else "f32"
    bound = getattr(engine.selection, "c", None)
    seg_delta = 0
    if seg_before is not None:
        seg_delta = _kernel_ops._segmented_topk._cache_size() - seg_before

    if _metrics.enabled():
        _M_QUERIES.labels(backend).inc(n_queries)
        _M_BATCHES.labels(backend).inc()
        _M_LAT.labels(backend, pow2_bucket(n_queries, min_bucket),
                      dtype).observe(t_end - clock.t0)
        for stage, seconds in clock.seconds.items():
            _M_STAGE.labels(stage).observe(seconds)
        if seg_delta > 0:
            _M_RECOMPILE.inc()
        if bound is not None:
            _M_EF_BOUND.set(bound)

    tracing = _trace.enabled()
    in_place = engine.in_place_keys
    # group the batch by (query key, routed key): every query in a group
    # pays the same elastic factor, so one observe/card per group amortizes
    # the host cost on large batches
    groups: dict[tuple[tuple[int, ...], tuple[int, ...]], int] = {}
    for qm, skey in zip(qmasks, routed):
        gk = (mask_key(qm), skey)
        groups[gk] = groups.get(gk, 0) + 1
    for (qkey, skey), count in groups.items():
        qsize = engine.table.closure_sizes.get(qkey)
        ssize = engine.selection.selected.get(skey)
        factor = None
        if qsize and ssize:
            factor = qsize / ssize
        if _metrics.enabled():
            if skey in in_place:
                _M_IN_PLACE.inc(count)
            if factor is not None:
                _M_EF.labels(backend).observe(factor, n=count)
                if bound is not None and factor < bound - 1e-12:
                    _M_EF_VIOL.inc(count)
            else:
                _M_UNSEEN.inc(count)
        if tracing:
            span_tier = (engine.seg_tier.get(skey)
                         if arena is not None else None)
            if tier_bucket is not None and span_tier is not None:
                q_bucket = tier_bucket.get((span_tier, skey in in_place))
            else:
                q_bucket = pow2_bucket(count, min_bucket)
            shortlist = None
            if arena is not None and arena.rerank is not None:
                lmax = span_tier if span_tier is not None else k
                shortlist = max(k, min(4 * k, lmax))
            _trace.get_tracer().add_card(_trace.QueryCard(
                query_key=qkey, selected_key=skey, n_queries=count,
                elastic_factor=factor, bound=bound, span_tier=span_tier,
                q_bucket=q_bucket, dtype=dtype, shortlist=shortlist,
                tombstone_density=tomb_density,
                recompiled=seg_delta > 0, backend=backend,
                scan=(None if span_tier is None else
                      "in_place" if skey in in_place else "row_table")))


def publish_engine_gauges(st) -> None:
    """Mirror an ``EngineStats`` into registry gauges so the exposition
    carries the engine's structural state (stats() keeps its dataclass
    shape; the registry is an additional read path, not a replacement)."""
    if not _metrics.enabled():
        return
    _M_ENGINE_GAUGE.labels("live").set(st.live_rows)
    _M_ENGINE_GAUGE.labels("tombstoned").set(st.tombstoned_rows)
    _M_ENGINE_GAUGE.labels("delta").set(st.delta_rows)
    _M_ENGINE_BYTES.labels("total").set(st.nbytes)
    _M_ENGINE_BYTES.labels("arena").set(st.arena_nbytes)
    _M_ENGINE_BYTES.labels("segment").set(st.segment_nbytes)
    _M_ENGINE_BYTES.labels("delta").set(st.delta_nbytes)
    _M_ENGINE_BYTES.labels("codes").set(st.codes_nbytes)
    _M_ENGINE_BYTES.labels("rerank").set(st.rerank_nbytes)
    _M_ENGINE_BYTES.labels("tombstone").set(st.tombstone_nbytes)
    _M_SELECTED.set(st.n_selected)
    _M_ENTRIES.set(st.total_entries)
    _M_ACHIEVED.set(st.achieved_c)


@dataclasses.dataclass
class EngineStats:
    n: int                       # dataset cardinality
    n_candidates: int            # candidate indices considered
    n_selected: int              # physical indexes built (incl. top)
    selection_cost: int          # Σ|I| excluding top (paper cost model)
    total_entries: int           # Σ|I| including top (actual rows stored)
    achieved_c: float            # min elastic factor over the workload
    select_seconds: float
    build_seconds: float
    nbytes: int                  # arena + segment table + private storage
    arena_nbytes: int = 0        # shared-arena share of nbytes (0 = no arena)
    segment_nbytes: int = 0      # CSR row-id table share of nbytes
    # streaming-mutation surface (DESIGN.md §3.6): a static engine reports
    # live_rows == n and zeros elsewhere; core.stream.StreamingEngine fills
    # the tombstone/delta breakdown
    live_rows: int = 0           # rows a search can return (base + delta)
    tombstoned_rows: int = 0     # deleted-but-not-yet-compacted rows
    delta_rows: int = 0          # rows resident in the delta arena
    arena_version: int = 0       # mutation/compaction counter of the arena
    delta_nbytes: int = 0        # delta-arena share of nbytes
    # tiered-precision surface (DESIGN.md §3.8): the arena's storage spec
    # and the per-tier byte split of arena_nbytes (+ the delta's tiers,
    # folded in by the streaming engine).  f32 engines report the vector
    # bytes under codes_nbytes (the scan tier IS the f32 rows)
    storage: str = "f32"         # arena storage spec ("int8+rerank", …)
    codes_nbytes: int = 0        # scan-tier rows (f32 / f16 / u8 codes)
    scales_nbytes: int = 0       # int8 per-row scale + zero-point columns
    rerank_nbytes: int = 0       # exact f32 rerank tier (0 = no rerank)
    tombstone_nbytes: int = 0    # packed delete bitmap(s)


def held_capacity(cap: int, need: int, headroom: float) -> int:
    """The length a held buffer keeps for ``need`` entries: ``cap`` while
    they fit, else ``need`` plus ``headroom`` of it, in whole bytes of a
    tombstone bitmap (8 rows) — so a buffer starts with headroom over its
    first length, grows by that fraction when outgrown, never shrinks."""
    if cap and need <= cap:
        return cap
    return max(8, -(-int(np.ceil(need * (1.0 + headroom))) // 8) * 8)


@dataclasses.dataclass
class ShapeReserve:
    """Device shapes a streaming engine holds across compactions
    (DESIGN.md §3.6).  Every compiled scan program is keyed on the
    arena's row count, the row table's length and its span tier, and a
    compaction with freshly labelled rows moves all three.  Held, the
    arena and the row table keep a capacity with headroom
    (:func:`held_capacity`), padded with rows and entries no segment
    addresses, and each selected key keeps its span tier while its
    segment fits in it — so a compaction compiles nothing while the
    shapes hold, and results are those of the unpadded engine bit for
    bit."""
    ROW_HEADROOM = 1 / 64       # row table; a segment's growth margin
    ARENA_HEADROOM = 1 / 512    # arena rows: ~1 MB at 1M x 128 f32

    rows: int = 0               # device row table length
    arena: int = 0              # arena row capacity
    tiers: dict = dataclasses.field(default_factory=dict)   # key -> tier

    def hold_rows(self, need: int) -> int:
        self.rows = held_capacity(self.rows, need, self.ROW_HEADROOM)
        return self.rows

    def hold_arena(self, need: int) -> int:
        self.arena = held_capacity(self.arena, need, self.ARENA_HEADROOM)
        return self.arena

    def hold_tier(self, key: tuple[int, ...], length: int) -> int:
        tier = self.tiers.get(key)
        if tier is None or length > tier:
            tier = self.tiers[key] = pow2_bucket(length)
        return tier


class LabelHybridEngine:
    """Build-once, search-many ELI engine over a pluggable index backend."""

    # bound on memoized fallback routes for query keys outside the selection
    # workload (a long-lived server fed diverse label combinations must not
    # grow host memory without limit; overflow keys are re-routed per batch)
    _ROUTE_CACHE_MAX = 65536

    def __init__(self, vectors: np.ndarray, label_sets: Sequence[tuple[int, ...]],
                 table: GroupTable, selection: EISResult,
                 sis_result: SISResult | None, backend: str, metric: str,
                 backend_params: dict, select_seconds: float,
                 storage: str = "f32"):
        self.sis_result = sis_result
        self.backend = backend
        self.metric = metric
        builder = get_index_builder(backend)
        self.backend_params = dict(backend_params)
        self._arena_native = hasattr(builder, "build_view")
        self._seg_backend = backend_params.get("kernel_backend", "ref")
        # fused scan stage (DESIGN.md §3.9): True | False | "auto";
        # resolved once so views, executor and warmup agree
        from ..kernels.fused_scan import resolve_fused
        self._seg_fused = resolve_fused(backend_params.get("fused", False),
                                        backend=self._seg_backend)
        parse_storage(storage)   # validate the spec before any device work
        if storage != "f32" and not self._arena_native:
            raise ValueError(
                f"storage={storage!r} needs an arena-native backend (the "
                f"compressed tiers live in the shared arena, DESIGN.md "
                f"§3.8); backend {backend!r} keeps private f32 copies")
        self.storage = storage

        self.indexes: dict[tuple[int, ...], object] = {}
        self.rows: dict[tuple[int, ...], np.ndarray] = {}
        self.segments: dict[tuple[int, ...], tuple[int, int]] = {}
        # held device shapes (streaming, :meth:`hold_shapes`); None: each
        # buffer is exactly as long as its contents
        self.reserve: ShapeReserve | None = None
        t0 = time.perf_counter()
        self.rebase(vectors, label_sets, table, selection)
        self._build_seconds = time.perf_counter() - t0
        self._select_seconds = select_seconds

    def rebase(self, vectors: np.ndarray,
               label_sets: Sequence[tuple[int, ...]], table: GroupTable,
               selection: EISResult, *, arena: Arena | None = None,
               label_words: np.ndarray | None = None,
               rows_hint: Mapping[tuple[int, ...], np.ndarray]
               | None = None) -> None:
        """Swap the dataset under the engine and rematerialize — the single
        home of dataset installation (``__init__`` is a rebase from
        nothing; streaming compaction folds tombstones + delta rows into a
        fresh arena and rebases through here, DESIGN.md §3.6).

        Every retained index/row table is dropped first: they are keyed to
        the OLD row numbering, and reusing them across a rebase would
        silently serve stale members (``apply_selection``'s incremental
        reuse is only sound while the dataset is fixed).  ``arena`` lets
        the caller install an already-folded device-resident arena (no
        host re-upload); ``label_words`` skips the host re-encode when the
        caller already holds the device-layout words; ``rows_hint`` seeds
        per-key member lists the caller already computed in the NEW row
        numbering (streaming compaction remaps the old segments instead of
        paying ``closure_members`` per key — the caller vouches they equal
        what the new table would produce).
        """
        self.vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        self.label_sets = list(label_sets)
        self.table = table
        if label_words is None:
            label_words = masks_to_int32_words(encode_many(self.label_sets))
        self.label_words = np.ascontiguousarray(label_words, dtype=np.int32)
        check_global_id_contract(len(self.label_sets))

        # stale across a dataset swap — apply_selection must rebuild all
        # (rows_hint entries are already in the new numbering and are the
        # one sanctioned carry-over)
        self.indexes, self.segments = {}, {}
        self.rows = dict(rows_hint) if rows_hint is not None else {}
        # Arena: the dataset's vectors/label words uploaded ONCE; views
        # reference them per segment.  Private-storage backends skip the
        # upload (their build copies rows as before).
        if not self._arena_native:
            self.arena: Arena | None = None
        else:
            self.arena = (arena if arena is not None
                          else Arena.from_host(self.vectors,
                                               self.label_words,
                                               storage=self.storage))
            if self.arena.storage != self.storage:
                raise ValueError(
                    f"installed arena holds {self.arena.storage!r} tiers "
                    f"but the engine is configured for {self.storage!r}")
        self.apply_selection(selection)

    def apply_selection(self, selection: EISResult) -> None:
        """(Re)materialize the engine for ``selection`` — the single home
        of segment-table + index + routing-table construction.

        Builds the CSR segment table (every selected index is an int32
        row-id segment of ONE concatenated ``rows_concat``), materializes
        arena views (zero-copy) or private-storage indexes (retained
        instances are reused — the incremental path of
        ``core.adaptive.AdaptiveEngine.reselect``), and refreshes the
        vectorized routing tables + fallback-route cache, which must never
        outlive the selection they were derived from.
        """
        import jax.numpy as jnp

        n = check_global_id_contract(len(self.label_sets))
        builder = get_index_builder(self.backend)
        old_rows, old_indexes = self.rows, self.indexes
        self.selection = selection
        self.indexes, self.rows, self.segments = {}, {}, {}
        parts, off = [], 0
        for key in selection.selected:
            rows = old_rows.get(key)
            if rows is None:
                rows = (np.arange(n, dtype=np.int64)
                        if key == EMPTY_KEY else
                        self.table.closure_members(key))
                rows = as_row_ids(rows, n)   # int32 + sentinel contract
            self.rows[key] = rows
            self.segments[key] = (off, rows.size)
            parts.append(rows)
            off += rows.size
        self.rows_concat = (np.concatenate(parts) if parts
                            else np.zeros(0, np.int32))
        # the span tier each key's launches use: its segment's
        # power-of-two bucket, or, with held shapes, the tier it held
        self.seg_tier = {
            key: (pow2_bucket(length) if self.reserve is None
                  else self.reserve.hold_tier(key, length))
            for key, (_, length) in self.segments.items()}
        # segments the unfused XLA scan reads in place (DESIGN.md §3.10):
        # exactly those whose row list is the identity arange(n) — the
        # root, whether built here or handed over through rows_hint
        self.in_place_keys = frozenset()
        if self._arena_native and _kernel_ops.in_place_capable(
                self._seg_backend, self._seg_fused):
            ident = np.arange(n, dtype=np.int32)
            self.in_place_keys = frozenset(
                key for key, rows in self.rows.items()
                if np.array_equal(rows, ident))
        # the device copy of the CSR table feeds the segmented kernel and
        # the views; private-storage backends never read it on device, so
        # they skip the upload (and its HBM) entirely.  Held, it is padded
        # to its capacity with entries no segment addresses
        table = self.rows_concat
        if self.reserve is not None:
            table = np.zeros(self.reserve.hold_rows(table.size), np.int32)
            table[:self.rows_concat.size] = self.rows_concat
        self._rows_concat_dev = (jnp.asarray(table)
                                 if self._arena_native else None)

        if self._arena_native and self.arena is not None:
            # views are zero-copy: re-materializing ALL of them on a new
            # selection costs a few µs each, no vector traffic
            for key, (start, length) in self.segments.items():
                self.indexes[key] = builder.build_view(
                    self.arena, self._rows_concat_dev, start, length,
                    metric=self.metric, **self.backend_params)
        else:
            for key, rows in self.rows.items():
                index = old_indexes.get(key)
                if index is None:
                    index = builder.build(
                        self.vectors[rows], self.label_words[rows],
                        metric=self.metric, **self.backend_params)
                self.indexes[key] = index

        # Routing table for the batched executor: the selected keys (in dict
        # order — route()'s tie-break order) as a dense uint64 mask matrix,
        # enabling one vectorized superset-matching pass per batch instead of
        # a per-query Python loop.  _route_cache memoizes fallback routing of
        # query keys outside the selection workload.
        self._skeys = list(selection.selected)   # always holds EMPTY_KEY
        self._skey_masks = np.stack([key_to_mask(k) for k in self._skeys])
        self._skey_sizes = np.array(
            [selection.selected[k] for k in self._skeys], dtype=np.int64)
        self._route_cache: dict[tuple[int, ...], tuple[int, ...]] = {}
        if _metrics.enabled():
            _M_SELECTED.set(len(self._skeys))
            _M_ENTRIES.set(selection.total_entries)
            c = getattr(selection, "c", None)
            if c is not None:
                _M_EF_BOUND.set(c)

    # -- construction --------------------------------------------------------
    @staticmethod
    def build(vectors: np.ndarray, label_sets: Sequence[tuple[int, ...]], *,
              mode: str = "eis", c: float = 0.2, space_budget: int | None = None,
              query_label_sets: Sequence[tuple[int, ...]] | None = None,
              backend: str = "flat", metric: str = "l2",
              sample_size: int | None = None, storage: str = "f32",
              **backend_params) -> "LabelHybridEngine":
        """Select indices (EIS at bound ``c`` or SIS under ``space_budget``)
        and materialize them.

        ``query_label_sets``: explicit workload; default derives candidates
        from all subsets of observed base label sets (paper default).
        ``sample_size``: use the §4.2 sampled closure-size estimator.
        ``storage``: arena tier spec (DESIGN.md §3.8) — ``"f32"`` (exact,
        the default), ``"fp16"``/``"int8"`` compressed scan tiers, or
        ``"fp16+rerank"``/``"int8+rerank"`` adding the exact in-program
        rerank stage; arena-native backends only.
        """
        t0 = time.perf_counter()
        qkeys = (observed_query_keys(query_label_sets)
                 if query_label_sets is not None else None)
        if sample_size is not None:
            table = sampled_group_table(label_sets, sample_size)
        else:
            table = GroupTable.build(label_sets, qkeys)

        sis_result: SISResult | None = None
        if mode == "eis":
            selection = greedy_eis(table.closure_sizes, c, qkeys)
        elif mode == "sis":
            if space_budget is None:
                raise ValueError("mode='sis' requires space_budget")
            sis_result = sis(table.closure_sizes, space_budget, qkeys)
            selection = sis_result.eis
        else:
            raise ValueError(f"unknown mode {mode!r}")
        select_seconds = time.perf_counter() - t0

        return LabelHybridEngine(vectors, label_sets, table, selection,
                                 sis_result, backend, metric, backend_params,
                                 select_seconds, storage=storage)

    @property
    def sentinel(self) -> int:
        """The empty-slot id: real ids live in [0, sentinel).  For a static
        engine this is the dataset cardinality; a streaming engine's grows
        with inserts (``core.stream.StreamingEngine.sentinel``)."""
        return len(self.label_sets)

    # -- routing --------------------------------------------------------------
    def route(self, query_label_set: tuple[int, ...]) -> tuple[int, ...]:
        """Selected index key serving this query (max elastic factor)."""
        qkey = mask_key(encode_label_set(query_label_set))
        hit = self.selection.assignment.get(qkey)
        if hit is not None:
            return hit
        # unseen query key: among selected keys ⊆ qkey pick the smallest
        # index (max elastic factor for the fixed |S(L_q)|)
        best, best_size = EMPTY_KEY, self.rows[EMPTY_KEY].size
        for skey, size in self.selection.selected.items():
            if key_contains(qkey, skey) and size < best_size:
                best, best_size = skey, size
        return best

    def route_many(self, query_label_sets: Sequence[tuple[int, ...]],
                   qmasks: np.ndarray | None = None) -> list[tuple[int, ...]]:
        """Vectorized :meth:`route` for a query batch.

        Assignment hits resolve through the selection table; the unseen
        remainder is deduplicated and routed in ONE superset-matching pass
        over the selected-key mask matrix (``(qmask & skey) == skey`` per
        uint64 word), picking the smallest containing index — identical to
        route()'s strict-< scan, argmin's first-minimum tie-break matching
        dict iteration order.  Results are memoized per key.

        ``qmasks``: optional pre-encoded ``encode_many(query_label_sets)``
        (callers that already encoded the batch skip a second pass).
        """
        if qmasks is None:
            qmasks = encode_many(query_label_sets)
        qkeys = [mask_key(m) for m in qmasks]
        routed: list[tuple[int, ...] | None] = [None] * len(qkeys)
        unseen: dict[tuple[int, ...], list[int]] = {}
        for qi, qkey in enumerate(qkeys):
            hit = self.selection.assignment.get(qkey)
            if hit is None:
                hit = self._route_cache.get(qkey)
            if hit is not None:
                routed[qi] = hit
            else:
                unseen.setdefault(qkey, []).append(qi)
        if unseen:
            um = np.stack([key_to_mask(kk) for kk in unseen])     # [U, W]
            sm = self._skey_masks[None, :, :]                     # [1, M, W]
            cand = np.all((um[:, None, :] & sm) == sm, axis=2)    # [U, M]
            sizes = np.where(cand, self._skey_sizes[None, :],
                             np.iinfo(np.int64).max)
            best = np.argmin(sizes, axis=1)
            best_size = sizes[np.arange(len(unseen)), best]
            top_size = self.rows[EMPTY_KEY].size
            for u, (qkey, qids) in enumerate(unseen.items()):
                chosen = (self._skeys[int(best[u])]
                          if best_size[u] < top_size else EMPTY_KEY)
                if len(self._route_cache) < self._ROUTE_CACHE_MAX:
                    self._route_cache[qkey] = chosen
                for qi in qids:
                    routed[qi] = chosen
        return routed

    # -- search ----------------------------------------------------------------
    def search(self, queries: np.ndarray,
               query_label_sets: Sequence[tuple[int, ...]], k: int,
               **search_params) -> tuple[np.ndarray, np.ndarray]:
        """Filtered top-k for a query batch.  Returns (dists, GLOBAL ids);
        id == N ⇒ empty slot.

        Delegates to the batched executor (:meth:`search_batched`) — the
        serving hot path; :meth:`search_looped` keeps the per-key reference
        loop for parity testing.
        """
        return self.search_batched(queries, query_label_sets, k,
                                   **search_params)

    @property
    def supports_lazy_deletes(self) -> bool:
        """True ⇔ every selected index can serve a pending-delete bitmap
        through ``search_padded(tomb=…)`` (the ``supports_tombstones``
        capability, ``index.base``).  Arena-native engines qualify by
        construction — the streaming executor fuses ``Arena.tombstones``
        into the segmented program; private-storage engines qualify when
        every materialized backend implements the mask natively."""
        if self._arena_native and self.arena is not None:
            return True
        return all(getattr(type(ix), "supports_tombstones", False)
                   for ix in self.indexes.values())

    def search_batched(self, queries: np.ndarray,
                       query_label_sets: Sequence[tuple[int, ...]], k: int,
                       *, min_bucket: int = 1, tomb_by_key=None,
                       **search_params) -> tuple[np.ndarray, np.ndarray]:
        """Batched multi-index executor (single-dispatch segmented form).

        1. routes the whole batch in one vectorized pass (route_many),
        2. **arena-native backends** (flat): queries are sorted by routed
           key and partitioned by their segment's power-of-two candidate
           span; each span tier becomes ONE call into the jit-cached
           segmented program (``kernels.ops.segmented_topk``) — every query
           carries its ``(start, len)`` segment of the engine's CSR row
           table, candidate rows are gathered from the shared arena, the
           label filter and ``lax.top_k`` are fused, and global ids come
           back from the device directly.  A 143-index selection costs
           O(#span tiers) ≈ O(log N) kernel launches per batch, not 143 —
           warm QPS no longer scales with the number of routed groups;
        3. **private-storage backends** (ivf / graph / distributed /
           third-party): per-group dispatch through the backend's jit-cached
           per-(index, k, bucket) ``search_padded`` as before, but the host
           defers materialization + the local→global id map until every
           group's device work is queued (single synchronization point),
           instead of blocking per group like the looped oracle.

        Bit-identical to :meth:`search_looped` on every backend: each query
        row's filtered top-k is independent of its batch neighbors, pad
        rows are sliced off, and the arena path runs byte-for-byte the same
        kernel as the views behind the looped executor (pinned by
        ``tests/test_search_padded_parity.py``).

        ``tomb_by_key`` (private-storage backends only; DESIGN.md §3.6):
        per-selected-key packed tombstone bitmaps over each index's LOCAL
        rows — ``core.stream.StreamingEngine`` derives them from its
        global dead mask so deletes stay lazy; keys absent from the
        mapping run their exact tombstone-free program.  The arena path
        rejects it: streaming drives ``Arena.tombstones`` through its own
        executor there.
        """
        telem = _metrics.enabled() or _trace.enabled()
        clock = StageClock(telem)
        with _trace.span("search.route", Q=len(queries),
                         backend=self.backend):
            queries = np.asarray(queries, dtype=np.float32)
            Q = queries.shape[0]
            # sentinel/dtype contract: ids int32, empty slot == n (asserted
            # here so third-party callers hit it before any device work)
            n = check_global_id_contract(len(self.label_sets))
            out_d = np.full((Q, k), np.inf, dtype=np.float32)
            out_i = np.full((Q, k), n, dtype=np.int32)
            if Q == 0:
                return out_d, out_i
            qmasks = encode_many(query_label_sets)
            qwords = masks_to_int32_words(qmasks)
            routed = self.route_many(query_label_sets, qmasks)
        clock.lap("route")
        pend: list[tuple[list[int], object, object, int]] = []

        if self._arena_native and self.arena is not None:
            if tomb_by_key is not None:
                raise TypeError(
                    "tomb_by_key is the private-storage lazy-delete path; "
                    "arena-native engines take the bitmap through "
                    "Arena.tombstones (core.stream)")
            if search_params:
                raise TypeError(f"arena-native backend {self.backend!r} "
                                f"takes no search params; got "
                                f"{sorted(search_params)}")
            seg_before = (_kernel_ops._segmented_topk._cache_size()
                          if telem else None)
            tier_bucket: dict[tuple[int, bool], int] = {}
            for qids, qp, lp, starts, lens, lmax, in_place, g in \
                    self.arena_tier_batches(queries, qwords, routed,
                                            min_bucket):
                clock.lap("pack")
                if telem:
                    tier_bucket[lmax, in_place] = qp.shape[0]
                with _trace.span("search.launch", lmax=lmax,
                                 bucket=qp.shape[0]):
                    vals, _, gi = _kernel_ops.segmented_topk(
                        qp, lp, self.arena.vectors, self.arena.label_words,
                        self.arena.norms, self._rows_concat_dev, starts,
                        lens, k=k, lmax=lmax, metric=self.metric,
                        backend=self._seg_backend, fused=self._seg_fused,
                        in_place=in_place, **self.scan_kwargs())
                clock.lap("launch")
                # global ids resolved inside the traced program (sentinel n
                # included): no host remap, and warmup covers the full path
                pend.append((qids, vals, gi, g))
            clock.lap("pack")   # the partition's last step
            # single synchronization point: every tier is already queued
            with _trace.span("search.sync"):
                for qids, d, gi, g in pend:
                    out_d[qids] = np.asarray(d)[:g]
                    out_i[qids] = np.asarray(gi)[:g]
            clock.lap("sync")
            if telem:
                with _trace.span("search.telemetry"):
                    record_search_telemetry(
                        self, routed, qmasks, k, Q, clock,
                        seg_before=seg_before, tier_bucket=tier_bucket,
                        min_bucket=min_bucket)
            return out_d, out_i

        by_key: dict[tuple[int, ...], list[int]] = {}
        for qi, key in enumerate(routed):
            by_key.setdefault(key, []).append(qi)
        for key, qids in by_key.items():
            index = self.indexes[key]
            searcher = getattr(index, "search_padded", None)
            if searcher is None:       # third-party, outside the registry
                searcher = functools.partial(fallback_search_padded, index)
            extra = search_params
            tomb = tomb_by_key.get(key) if tomb_by_key else None
            if tomb is not None:
                extra = dict(search_params, tomb=tomb)
            with _trace.span("search.launch", queries=len(qids)):
                d, li = dispatch_padded(searcher, queries[qids],
                                        qwords[qids], k,
                                        min_bucket=min_bucket, **extra)
            pend.append((qids, d, li, len(qids)))
        clock.lap("launch")

        # deferred sync: every group's device work is queued before the
        # first host materialization, so XLA executes groups while the
        # host maps the finished ones (the looped oracle blocks per group)
        with _trace.span("search.sync"):
            for qids, d, li, g in pend:
                rows = self.rows[routed[qids[0]]]
                li = np.asarray(li)[:g]
                if rows.size:
                    empty = li >= rows.size
                    gi = np.where(empty, n,
                                  rows[np.clip(li, 0, rows.size - 1)])
                    out_i[qids] = gi.astype(np.int32)
                # rows.size == 0 (empty dataset edge): out_i already holds
                # the sentinel n everywhere, nothing to map
                out_d[qids] = np.asarray(d)[:g]
        clock.lap("sync")
        if telem:
            with _trace.span("search.telemetry"):
                record_search_telemetry(self, routed, qmasks, k, Q, clock,
                                        min_bucket=min_bucket)
        return out_d, out_i

    def arena_tier_batches(self, queries: np.ndarray, qwords: np.ndarray,
                           routed: Sequence[tuple[int, ...]],
                           min_bucket: int = 1):
        """Partition a routed batch by candidate-span tier and yield the
        padded segmented-program operands per tier:

            (qids, qp, lp, starts, lens, lmax, in_place, g)

        — queries sorted by segment start within a tier (gather locality),
        zero-padded to the power-of-two Q-bucket, with each query's
        ``(start, len)`` CSR segment.  Queries routed to an in-place
        segment (``in_place_keys``) form a batch of their own within
        their span tier, yielded with ``in_place=True``.  The single home
        of the arena executor's partition+padding convention:
        ``search_batched`` and the streaming engine's tombstone-aware
        executor (``core.stream.StreamingEngine``) both iterate it, so the
        two executors run the identical tier/bucket decomposition by
        construction.  Each tier's sort and padding, and the partition
        before them, is a ``search.pack`` span, closed before the yield."""
        tiers: dict[tuple[int, bool], list[int]] = {}
        with _trace.span("search.pack"):
            for qi, key in enumerate(routed):
                tiers.setdefault((self.seg_tier[key],
                                  key in self.in_place_keys), []).append(qi)
        for lmax, in_place in sorted(tiers):
            with _trace.span("search.pack", lmax=lmax):
                qids = sorted(tiers[lmax, in_place],
                              key=lambda qi: self.segments[routed[qi]][0])
                g = len(qids)
                bucket = pow2_bucket(g, min_bucket)
                qp = np.zeros((bucket, queries.shape[1]), np.float32)
                qp[:g] = queries[qids]
                lp = np.zeros((bucket, qwords.shape[1]), np.int32)
                lp[:g] = qwords[qids]
                seg = np.zeros((2, bucket), np.int32)   # starts / lens
                seg[:, :g] = np.array(
                    [self.segments[routed[qi]] for qi in qids], np.int32).T
            yield qids, qp, lp, seg[0], seg[1], lmax, in_place, g

    def search_looped(self, queries: np.ndarray,
                      query_label_sets: Sequence[tuple[int, ...]], k: int,
                      tomb_by_key=None,
                      **search_params) -> tuple[np.ndarray, np.ndarray]:
        """Reference executor: per-key Python loop, one un-bucketed backend
        call per selected index (the pre-batching code path, kept as the
        parity oracle for :meth:`search_batched` — including the
        per-selected-key ``tomb_by_key`` lazy-delete bitmaps)."""
        queries = np.asarray(queries, dtype=np.float32)
        Q = queries.shape[0]
        n = len(self.label_sets)
        out_d = np.full((Q, k), np.inf, dtype=np.float32)
        out_i = np.full((Q, k), n, dtype=np.int32)

        qwords = masks_to_int32_words(encode_many(query_label_sets))
        by_key: dict[tuple[int, ...], list[int]] = {}
        for qi, qls in enumerate(query_label_sets):
            by_key.setdefault(self.route(tuple(qls)), []).append(qi)

        for key, qids in by_key.items():
            index = self.indexes[key]
            rows = self.rows[key]
            extra = search_params
            tomb = tomb_by_key.get(key) if tomb_by_key else None
            if tomb is not None:
                extra = dict(search_params, tomb=tomb)
            d, li = index.search(queries[qids], qwords[qids], k, **extra)
            li = np.asarray(li)
            empty = li >= rows.size
            gi = np.where(empty, n, rows[np.clip(li, 0, rows.size - 1)])
            out_d[qids] = d
            out_i[qids] = gi.astype(np.int32)
        return out_d, out_i

    # -- warmup ----------------------------------------------------------------
    def warmup(self, ks: Sequence[int], buckets: Sequence[int],
               tomb_variants: bool = False, **search_params) -> dict:
        """Pre-trace the per-(k, bucket) dispatch tables ahead of traffic.

        Cold serving latency is dominated by tracing + XLA compilation of
        every search program the first batch touches (exp9 measured 11.8 s
        on the distributed backend's first batched call).  ``warmup`` runs
        each program once on zero queries so first real batches hit the
        executable cache:

          * arena-native backends: the segmented program for every
            (k ∈ ks, Q-bucket ∈ buckets, candidate-span tier) triple — span
            tiers are known at build time from the segment table — in the
            read path the executor takes there: in place for the tiers of
            ``in_place_keys``, through the row table for tiers that hold
            other segments (those also serve the per-view looped path);
          * private-storage backends: every selected index's
            ``search_padded`` per (k, bucket).

        ``buckets`` are Q-buckets (rounded up to powers of two); a server
        passes the buckets its batch-size distribution produces.  Returns
        ``{"seconds", "programs"}``.

        ``tomb_variants=True`` (streaming, private-storage backends) also
        traces each index's tombstone-masked program on an all-zero
        bitmap, so the first post-delete batch pays no retrace either
        (the arena analogue lives in ``StreamingEngine.warmup``).
        """
        import jax
        import jax.numpy as jnp

        from ..index.base import tombstone_bytes

        t0 = time.perf_counter()
        D = self.vectors.shape[1]
        W = self.label_words.shape[1]
        outs: list[object] = []
        span_tiers = self.span_tiers()
        for k in ks:
            for b in buckets:
                bucket = pow2_bucket(b)
                qz = np.zeros((bucket, D), np.float32)
                lz = np.zeros((bucket, W), np.int32)
                if self._arena_native and self.arena is not None:
                    zero = jnp.zeros(bucket, jnp.int32)
                    for lmax, in_place in span_tiers:
                        vals, _, _ = _kernel_ops.segmented_topk(
                            qz, lz, self.arena.vectors,
                            self.arena.label_words, self.arena.norms,
                            self._rows_concat_dev, zero, zero, k=k,
                            lmax=lmax, metric=self.metric,
                            backend=self._seg_backend, fused=self._seg_fused,
                            in_place=in_place, **self.scan_kwargs())
                        outs.append(vals)
                else:
                    for index in self.indexes.values():
                        searcher = getattr(index, "search_padded", None)
                        if searcher is None:
                            searcher = functools.partial(
                                fallback_search_padded, index)
                        d, _ = searcher(qz, lz, k, **search_params)
                        outs.append(d)
                        if tomb_variants and getattr(
                                type(index), "supports_tombstones", False):
                            zt = np.zeros(
                                tombstone_bytes(index.num_vectors), np.uint8)
                            d, _ = searcher(qz, lz, k, tomb=zt,
                                            **search_params)
                            outs.append(d)
        for o in outs:
            jax.block_until_ready(jnp.asarray(o))
        return {"seconds": time.perf_counter() - t0, "programs": len(outs)}

    def span_tiers(self) -> list[tuple[int, bool]]:
        """The (span tier, in place) launches ``arena_tier_batches`` can
        yield for this selection — what the warm-ups compile.  With held
        shapes, a segment within its growth margin
        (``ShapeReserve.ROW_HEADROOM``, and never past the arena's rows)
        of its tier's top can reach the tier above, which is listed too."""
        tiers = {(tier, key in self.in_place_keys)
                 for key, tier in self.seg_tier.items()}
        if self.reserve is not None:
            for key, (_, length) in self.segments.items():
                reach = min(int(np.ceil(length
                                        * (1 + ShapeReserve.ROW_HEADROOM))),
                            self.arena.n)
                if reach > self.seg_tier[key]:
                    tiers.add((pow2_bucket(reach), key in self.in_place_keys))
        return sorted(tiers)

    def scan_kwargs(self) -> dict:
        """The arena operands of every base-scan launch besides its rows:
        the storage tiers, and, where the arena is held past the dataset,
        the dataset's cardinality as the empty-slot id."""
        kw = self.arena.tier_kwargs()
        if self.reserve is not None:
            kw["n_rows"] = np.int32(len(self.label_sets))
        return kw

    def hold_shapes(self) -> None:
        """Hold the device shapes across rebases (:class:`ShapeReserve`):
        pad the arena and the row table to capacities with headroom and
        keep each key's span tier from here on.  Arena-native engines
        only; a second call changes nothing."""
        if self.reserve is not None or self.arena is None:
            return
        self.reserve = ShapeReserve()
        self.arena = self.arena.padded(
            self.reserve.hold_arena(len(self.label_sets)))
        self.apply_selection(self.selection)

    def warmup_serving(self, ks: Sequence[int], min_bucket: int,
                       max_batch: int, **search_params) -> dict:
        """Serving-shaped :meth:`warmup`: pre-trace every (k, Q-bucket)
        program a bucket-aware micro-batcher can dispatch — the full
        power-of-two ladder from ``min_bucket`` to ``max_batch``
        (``index.base.serving_buckets``), not just the buckets one request
        list happens to produce.  After this, a runtime coalescing batches
        of any size ≤ ``max_batch`` adds zero new search traces (the
        zero-per-request-compilation invariant the serving runtime
        asserts)."""
        from ..index.base import serving_buckets
        return self.warmup(ks, serving_buckets(min_bucket, max_batch),
                           **search_params)

    # -- reporting --------------------------------------------------------------
    def stats(self) -> EngineStats:
        qkeys = [k for k in self.table.closure_sizes if k != EMPTY_KEY]
        achieved = min_elastic_factor(qkeys, self.table.closure_sizes,
                                      self.selection.selected)
        arena_nbytes = self.arena.nbytes if self.arena is not None else 0
        tiers = (self.arena.tier_nbytes if self.arena is not None
                 else {"codes": 0, "scales": 0, "rerank": 0, "tombstone": 0})
        # the CSR table is device-resident only on arena-native backends;
        # private-storage accounting stays comparable to pre-arena runs
        segment_nbytes = (int(self._rows_concat_dev.nbytes)
                          if self._rows_concat_dev is not None else 0)
        st = EngineStats(
            n=len(self.label_sets),
            n_candidates=len(self.table.closure_sizes),
            n_selected=len(self.indexes),
            selection_cost=self.selection.cost,
            total_entries=self.selection.total_entries,
            achieved_c=achieved,
            select_seconds=self._select_seconds,
            build_seconds=self._build_seconds,
            # arena + CSR segment table counted once; views report nbytes=0,
            # private-storage backends report their copies as before
            nbytes=(arena_nbytes + segment_nbytes
                    + sum(ix.nbytes for ix in self.indexes.values())),
            arena_nbytes=arena_nbytes,
            segment_nbytes=segment_nbytes,
            live_rows=len(self.label_sets),
            arena_version=(self.arena.version
                           if self.arena is not None else 0),
            storage=self.storage,
            codes_nbytes=tiers["codes"],
            scales_nbytes=tiers["scales"],
            rerank_nbytes=tiers["rerank"],
            tombstone_nbytes=tiers["tombstone"],
        )
        publish_engine_gauges(st)
        return st


def brute_force_filtered(vectors: np.ndarray,
                         label_sets: Sequence[tuple[int, ...]],
                         queries: np.ndarray,
                         query_label_sets: Sequence[tuple[int, ...]],
                         k: int, metric: str = "l2"
                         ) -> tuple[np.ndarray, np.ndarray]:
    """Exact filtered ground truth (benchmark reference)."""
    import jax.numpy as jnp
    from ..kernels import ref

    lx = masks_to_int32_words(encode_many(label_sets))
    lq = masks_to_int32_words(encode_many(query_label_sets))
    d, i = ref.filtered_topk(jnp.asarray(queries, jnp.float32),
                             jnp.asarray(vectors, jnp.float32),
                             jnp.asarray(lq), jnp.asarray(lx), k, metric)
    return np.asarray(d), np.asarray(i)


def recall_at_k(result_ids: np.ndarray, truth_ids: np.ndarray, n: int) -> float:
    """Paper §2.1 recall: |result ∩ truth| / |truth| (averaged over queries;
    id == n means an empty slot and is ignored)."""
    total, hit = 0, 0
    for r, t in zip(result_ids, truth_ids):
        tt = set(int(v) for v in t if v < n)
        if not tt:
            continue
        rr = set(int(v) for v in r if v < n)
        hit += len(rr & tt)
        total += len(tt)
    return hit / total if total else 1.0
