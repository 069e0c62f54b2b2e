"""StreamingEngine — the streaming mutation subsystem (DESIGN.md §3.6).

The paper's selected indexes are closures over ONE static dataset; every
serving scenario the ROADMAP targets mutates.  This module wraps
:class:`~repro.core.engine.LabelHybridEngine` with ``insert`` / ``delete``
/ ``flush`` while keeping search results **bit-identical to an engine
rebuilt from scratch on the surviving rows** — the correctness oracle for
the whole subsystem (pinned by tests/test_streaming_engine.py and the
hypothesis interleavings in tests/test_streaming_properties.py).

Id space: base rows keep their ids ``[0, N)``; inserted rows are assigned
``N, N+1, …`` in arrival order; the empty-slot sentinel is
:attr:`sentinel` (= ``N + #inserted``, the stream cardinality).  A
compaction renumbers survivors compactly (stream order preserved) and
reports the old→new ``id_map``.

Two capability tiers, mirroring the ``build_view`` split in
``index/base.py``:

  * **arena-native backends** (flat) absorb mutations lazily: deletes set
    bits in the base arena's packed tombstone bitmap (fused into the
    segmented program's label filter — one extra AND, no new dispatch
    key); inserts append into a fixed-capacity :class:`DeltaArena`
    (power-of-two capacity tiers) without touching the CSR segment table.
    Search runs base (tombstone-masked) + delta (brute-force scan, the
    SAME segmented program over an identity row table) and merges top-k
    **in-program** preserving the (distance, global-id) tie-break
    (``kernels.ops.merge_topk``).  Exactness of PostFiltering inside any
    routed superset-key index makes the merged result independent of
    routing — which is why parity with a from-scratch rebuild holds with
    mutations still pending.
  * **private-storage backends** (ivf / graph / distributed): DELETES are
    lazy here too (ISSUE 5) — the engine derives one packed bitmap per
    selected index from its global dead mask and passes it through the
    ``search_padded(tomb=…)`` protocol (``index.base``), where each
    backend fuses it into its filter natively (IVF widens its probe
    waves over dead rows; the graph walks them for connectivity but
    excludes them from results; distributed shards the bitmap alongside
    its rows).  Only INSERTS (which these structures cannot absorb
    in-place) and the compaction triggers force the fold — a
    deterministic full re-build with the original build arguments, whose
    seeded determinism gives rebuilt-from-scratch parity.  The
    lazy-delete invariant is necessarily the *fixed-structure* one
    (DESIGN.md §3.6): results are bit-identical to the same engine with
    the dead rows failing the filter — for exhaustive backends
    (flat / distributed) that coincides with the rebuilt-engine oracle;
    for approximate structures (ivf / graph) a rebuild re-clusters /
    re-wires and is *not* bit-comparable, pending or folded being equally
    approximate (measured: ~98% of fixture queries differ from exact
    ground truth on ivf at nprobe=4 — structure dependence is inherent,
    not introduced by tombstones).

Compaction (``flush`` or the automatic thresholds) folds live delta rows
and drops tombstoned rows into a fresh base arena, updates the GroupTable
incrementally (``GroupTable.compacted`` — no O(Σ 2^|G|) re-expansion),
remaps the old segments instead of recomputing per-key closures
(``rebase(rows_hint=…)``), and rebases the engine through its single
dataset-installation path (``LabelHybridEngine.rebase`` →
``apply_selection``) — measured ~9× faster than a full rebuild
(BENCH_exp10.json).  When a
:class:`WorkloadMonitor` is attached and its drift exceeds the threshold,
the compaction piggybacks a weighted reselect (``core.adaptive``) on the
already-paid rebuild — otherwise the current selection's keys are kept
with refreshed sizes.
"""
from __future__ import annotations

import time
from typing import Sequence

import numpy as np

from ..index.base import (Arena, CapacityError, DeltaArena,
                          MIN_DELTA_CAPACITY, as_row_ids,
                          check_global_id_contract, pack_tombstones,
                          pow2_bucket)
from ..kernels import ops as _kernel_ops
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .adaptive import WorkloadMonitor, selection_from_weighted, weighted_select
from .eis import EISResult
from .engine import (LabelHybridEngine, StageClock, publish_engine_gauges,
                     record_search_telemetry)
from .faults import faultpoint, register_fault_point
from .groups import EMPTY_KEY, GroupTable
from .labels import encode_many, key_to_mask, masks_to_int32_words

# crash site inside the compaction: survivors computed, nothing rebased
# yet — the in-memory engine is mid-fold and must be recoverable from the
# durable state alone (core/durability.py; tests/test_crash_matrix.py)
register_fault_point("compact.mid_fold",
                     "flush(): after _survivors, before the fold")

# Streaming-mutation telemetry (DESIGN.md §6.3): host-side counters and
# gauges only — the mutation/search device programs are untouched.
_M_MUT = _metrics.counter(
    "eli_stream_mutations_total", "streaming mutations by operation",
    ("op",),
)
_M_MUT_ROWS = _metrics.counter(
    "eli_stream_rows_total",
    "rows moved by streaming mutations (inserted/deleted/folded/dropped)",
    ("op",),
)
_M_MUT_S = _metrics.histogram(
    "eli_stream_mutation_seconds", "streaming mutation wall time", ("op",),
)
_M_RESELECTS = _metrics.counter(
    "eli_stream_reselects_total",
    "drift-triggered reselects piggybacked on a compaction",
)
_M_LIVE = _metrics.gauge(
    "eli_stream_live_rows", "rows a streaming search can return",
)
_M_TOMB = _metrics.gauge(
    "eli_stream_tombstoned_rows", "deleted-but-not-yet-compacted rows",
)
_M_DELTA = _metrics.gauge(
    "eli_stream_delta_rows", "rows resident in the delta arena / staging",
)


class StreamingEngine:
    """Mutable façade over a ``LabelHybridEngine`` (DESIGN.md §3.6)."""

    def __init__(self, engine: LabelHybridEngine, *,
                 max_delta_fraction: float | None = 0.25,
                 max_tombstone_fraction: float | None = 0.25,
                 min_delta_capacity: int = MIN_DELTA_CAPACITY,
                 max_delta_capacity: int | None = None,
                 monitor: WorkloadMonitor | None = None,
                 drift_threshold: float = 0.25,
                 min_queries: int = 200,
                 space_budget: int | None = None,
                 build_kwargs: dict | None = None,
                 lazy_deletes: bool = True):
        self.base = engine
        self.max_delta_fraction = max_delta_fraction
        self.max_tombstone_fraction = max_tombstone_fraction
        self.min_delta_capacity = min_delta_capacity
        self.max_delta_capacity = max_delta_capacity
        # escape hatch (and the exp10 A/B baseline): False restores the
        # PR 4 fold-per-delete behavior on private-storage backends
        self._lazy_deletes = lazy_deletes
        self.monitor = monitor
        self.drift_threshold = drift_threshold
        self.min_queries = min_queries
        self.space_budget = space_budget
        # fold replay arguments for the private-storage path: the fold IS a
        # from-scratch build on the survivors, so it must reuse the original
        # construction arguments verbatim (determinism ⇒ parity)
        self._build_kwargs = dict(build_kwargs) if build_kwargs else dict(
            mode="eis", c=engine.selection.c, backend=engine.backend,
            metric=engine.metric, storage=engine.storage,
            **engine.backend_params)
        self.compaction_log: list[dict] = []
        if self.lazy:
            # compactions move the arena's rows, the row table's length
            # and segments' span tiers, each a shape of every scan
            # program: held, a compaction compiles nothing
            engine.hold_shapes()
        self._reset_staging()

    # -- construction ---------------------------------------------------------
    @staticmethod
    def build(vectors: np.ndarray, label_sets: Sequence[tuple[int, ...]], *,
              max_delta_fraction: float | None = 0.25,
              max_tombstone_fraction: float | None = 0.25,
              min_delta_capacity: int = MIN_DELTA_CAPACITY,
              max_delta_capacity: int | None = None,
              monitor: WorkloadMonitor | None = None,
              drift_threshold: float = 0.25,
              min_queries: int = 200,
              space_budget: int | None = None,
              lazy_deletes: bool = True,
              **build_kwargs) -> "StreamingEngine":
        """Build the base ``LabelHybridEngine`` (same kwargs as
        ``LabelHybridEngine.build``) and wrap it for streaming."""
        engine = LabelHybridEngine.build(vectors, label_sets, **build_kwargs)
        return StreamingEngine(
            engine, max_delta_fraction=max_delta_fraction,
            max_tombstone_fraction=max_tombstone_fraction,
            min_delta_capacity=min_delta_capacity,
            max_delta_capacity=max_delta_capacity, monitor=monitor,
            drift_threshold=drift_threshold, min_queries=min_queries,
            space_budget=space_budget, build_kwargs=build_kwargs,
            lazy_deletes=lazy_deletes)

    def _reset_staging(self) -> None:
        eng = self.base
        self._base_dead = np.zeros(len(eng.label_sets), dtype=bool)
        self._delta_dead = np.zeros(0, dtype=bool)
        self._delta_vec_parts: list[np.ndarray] = []
        self._delta_lw_parts: list[np.ndarray] = []
        self._delta_ls: list[tuple[int, ...]] = []
        self._n_inserted = 0
        self._dirty = False          # private-storage fold pending (inserts)
        self._has_base_tombs = False  # any base delete since last compaction
        self._tomb_by_key = None     # per-selected-key bitmaps (private lazy)
        if self.lazy:
            # the delta holds the SAME tiers as the base arena (inserts
            # quantize eagerly at append, DESIGN.md §3.8) so compaction
            # re-folds per tier without a representation change
            self.delta = DeltaArena.empty(eng.vectors.shape[1],
                                          eng.label_words.shape[1],
                                          self.min_delta_capacity,
                                          storage=eng.storage,
                                          max_capacity=self.max_delta_capacity)
        else:
            self.delta = None

    # -- properties -----------------------------------------------------------
    @property
    def lazy(self) -> bool:
        """True ⇔ the base backend is arena-native, i.e. mutations are
        absorbed lazily (tombstone mask + delta scan) instead of folded
        before the next search."""
        return self.base._arena_native and self.base.arena is not None

    @property
    def lazy_deletes_active(self) -> bool:
        """True ⇔ base deletes on a private-storage backend are served
        through per-index ``search_padded(tomb=…)`` bitmaps instead of a
        fold-before-search (ISSUE 5).  Arena-native backends have their
        own (always-on) lazy path and report False here."""
        return (not self.lazy and self._lazy_deletes
                and self.base.supports_lazy_deletes)

    @property
    def sentinel(self) -> int:
        """Empty-slot id == stream cardinality (base + all inserts since
        the last compaction, including tombstoned ones)."""
        return len(self.base.label_sets) + self._n_inserted

    @property
    def vectors(self) -> np.ndarray:
        return self.base.vectors

    @property
    def label_sets(self) -> list[tuple[int, ...]]:
        """Label set per live-or-dead stream id (base then delta) — the
        array a returned id indexes into."""
        return list(self.base.label_sets) + self._delta_ls

    def label_set(self, gid: int) -> tuple[int, ...]:
        n_base = len(self.base.label_sets)
        return (tuple(self.base.label_sets[gid]) if gid < n_base
                else tuple(self._delta_ls[gid - n_base]))

    # -- mutations ------------------------------------------------------------
    def insert(self, vectors: np.ndarray,
               label_sets: Sequence[tuple[int, ...]]) -> np.ndarray:
        """Insert rows; returns their assigned global stream ids.

        Arena-native: appends into the device delta arena (one
        dynamic-update-slice per power-of-two batch tier, never a
        retrace).  Private-storage: stages host-side until the next fold.
        If this batch would push the delta past ``max_delta_fraction``,
        the pending state is compacted FIRST (see ``compaction_log`` for
        the renumbering of earlier ids) and the batch lands in the fresh
        delta — the ids returned are therefore always valid at return.
        A ``stream.insert`` span, holding the ``stream.compact`` of such a
        flush and the ``stream.delta_append``.
        """
        with _trace.span("stream.insert"):
            return self._insert(vectors, label_sets)

    def _insert(self, vectors: np.ndarray,
                label_sets: Sequence[tuple[int, ...]]) -> np.ndarray:
        _t0 = time.perf_counter() if _metrics.enabled() else 0.0
        vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.base.vectors.shape[1]:
            raise ValueError(f"expected [m, {self.base.vectors.shape[1]}] "
                             f"vectors, got {vectors.shape}")
        label_sets = [tuple(ls) for ls in label_sets]
        if len(label_sets) != vectors.shape[0]:
            raise ValueError("one label set per inserted vector required")
        m = vectors.shape[0]
        if m == 0:
            return np.zeros(0, dtype=np.int64)
        if (self.max_delta_fraction is not None
                and self._n_inserted + m > self.max_delta_fraction
                * max(1, len(self.base.label_sets))):
            self.flush()
        check_global_id_contract(self.sentinel + m)   # sentinel must fit
        lw = masks_to_int32_words(encode_many(label_sets))
        ids = np.arange(self.sentinel, self.sentinel + m, dtype=np.int64)

        # the functional append runs FIRST: it is the step that can raise
        # (typed CapacityError at the max_delta_capacity ceiling), and a
        # failed insert must leave the engine bit-for-bit unchanged — no
        # half-staged host parts, no advanced cursor
        new_delta = None
        if self.lazy:
            with _trace.span("stream.delta_append"):
                new_delta = self.delta.appended(vectors, lw)
        self._delta_vec_parts.append(vectors)
        self._delta_lw_parts.append(lw)
        self._delta_ls.extend(label_sets)
        self._delta_dead = np.concatenate(
            [self._delta_dead, np.zeros(m, dtype=bool)])
        self._n_inserted += m
        if self.lazy:
            self.delta = new_delta
        else:
            self._dirty = True
        self._record_mutation("insert", m, _t0)
        return ids

    def ensure_insert_capacity(self, m: int) -> None:
        """Raise :class:`CapacityError` iff ``insert`` of ``m`` rows would
        — after any delta-fill flush the insert itself would trigger —
        exceed ``max_delta_capacity``.  State is never touched; the
        durability layer calls this BEFORE logging a record so the WAL
        only ever holds mutations whose replay succeeds."""
        if m == 0 or not self.lazy or self.max_delta_capacity is None:
            return
        will_flush = (self.max_delta_fraction is not None
                      and self._n_inserted + m > self.max_delta_fraction
                      * max(1, len(self.base.label_sets)))
        count = 0 if will_flush else self.delta.count
        need = count + pow2_bucket(m)
        if need > pow2_bucket(self.max_delta_capacity):
            raise CapacityError(
                f"inserting {m} rows needs delta capacity {need} "
                f"(max_delta_capacity {self.max_delta_capacity})")

    def delete(self, ids) -> int:
        """Tombstone rows by global stream id; returns how many were newly
        deleted (repeat deletes are idempotent no-ops).  Lazy on EVERY
        registered backend (ISSUE 5): arena-native engines re-pack +
        upload the arena bitmap (⌈N/8⌉ bytes) and fuse it into the very
        next search's filter; private-storage engines invalidate their
        per-selected-key bitmaps, re-derived at the next search —
        O(Σ|I|/8) host bytes, never O(build).  Staged-delta deletes ride
        the fold their insert already forced.  May trigger automatic
        compaction.  A ``stream.delete`` span, holding the
        ``stream.tomb_upload`` and any ``stream.compact``."""
        with _trace.span("stream.delete"):
            return self._delete(ids)

    def _delete(self, ids) -> int:
        _t0 = time.perf_counter() if _metrics.enabled() else 0.0
        ids = np.unique(np.asarray(ids, dtype=np.int64).ravel())
        if ids.size == 0:
            return 0
        n_base = len(self.base.label_sets)
        if ids.size and (ids.min() < 0 or ids.max() >= self.sentinel):
            raise ValueError(f"ids outside [0, {self.sentinel})")
        base_ids = ids[ids < n_base]
        delta_slots = ids[ids >= n_base] - n_base
        newly = int((~self._base_dead[base_ids]).sum()
                    + (~self._delta_dead[delta_slots]).sum())
        if newly == 0:
            return 0
        self._base_dead[base_ids] = True
        self._delta_dead[delta_slots] = True
        if self.lazy:
            with _trace.span("stream.tomb_upload"):
                if base_ids.size:
                    self.base.arena = self.base.arena.with_tombstones(
                        self._base_dead)
                    self._has_base_tombs = True
                if delta_slots.size:
                    self.delta = self.delta.with_tombstones(
                        self._delta_dead)
        elif base_ids.size:
            if self.lazy_deletes_active:
                self._has_base_tombs = True
                self._tomb_by_key = None     # re-derive at next search
            else:
                self._dirty = True
        # non-lazy delta_slots: those rows are staged host-side and only
        # become searchable at the fold their insert made pending
        # (_dirty) — the fold reads _delta_dead, nothing else to do
        self._record_mutation("delete", newly, _t0)
        self._maybe_compact()
        return newly

    def _private_tombs(self) -> dict | None:
        """Per-selected-key packed bitmaps for the private-storage lazy
        path, derived from the global base dead mask through each key's
        member-row table (``engine.rows`` — local row r of index I(key)
        is global row rows[key][r], the id space the backend's ``tomb``
        contract speaks).  Keys with no dead member stay absent so their
        groups run the exact tombstone-free program.  Cached until the
        next delete/compaction."""
        if not self._has_base_tombs:
            return None
        if self._tomb_by_key is None:
            tombs = {}
            for key, rows in self.base.rows.items():
                dead = self._base_dead[rows]
                if dead.any():
                    tombs[key] = pack_tombstones(dead)
            self._tomb_by_key = tombs
        return self._tomb_by_key

    # -- compaction -----------------------------------------------------------
    def _maybe_compact(self) -> None:
        """Deleted-fraction trigger (the delta-fill trigger runs at the
        TOP of ``insert`` so freshly returned ids are never invalidated
        by the very call that produced them)."""
        dead = int(self._base_dead.sum() + self._delta_dead.sum())
        if (self.max_tombstone_fraction is not None
                and dead > self.max_tombstone_fraction
                * max(1, self.sentinel)):
            self.flush()

    def _survivors(self):
        """(alive_base, alive_delta, id_map, new_label_sets) for the
        current mutation state; survivors keep stream order, so the
        old→new renumbering is monotonic — the property the merged
        (distance, id) tie-break's parity with a rebuild relies on."""
        eng = self.base
        n_base = len(eng.label_sets)
        alive_base = ~self._base_dead
        alive_delta = ~self._delta_dead
        nb, nd = int(alive_base.sum()), int(alive_delta.sum())
        id_map = np.full(n_base + self._n_inserted, -1, dtype=np.int64)
        id_map[:n_base][alive_base] = np.arange(nb)
        id_map[n_base:][alive_delta] = nb + np.arange(nd)
        new_ls = ([ls for ls, a in zip(eng.label_sets, alive_base) if a]
                  + [ls for ls, a in zip(self._delta_ls, alive_delta) if a])
        return alive_base, alive_delta, id_map, new_ls

    def flush(self) -> dict:
        """Compact now: fold live delta rows in, drop tombstoned rows,
        renumber survivors (report carries the ``id_map``), optionally
        piggyback a drift-triggered reselect.  Returns the report (also
        appended to ``compaction_log``).  A ``stream.compact`` span: the
        survivors, the incremental GroupTable, the fold, the upload and
        the rebase."""
        with _trace.span("stream.compact"):
            return self._flush()

    def _flush(self) -> dict:
        t0 = time.perf_counter()
        eng = self.base
        alive_base, alive_delta, id_map, new_ls = self._survivors()
        faultpoint("compact.mid_fold")
        dropped = int((~alive_base).sum() + (~alive_delta).sum())
        folded = int(alive_delta.sum())
        reselected = False
        if self.lazy:
            if self._n_inserted or dropped:   # mutation-free flush: no-op
                reselected = self._compact_lazy(alive_base, alive_delta,
                                                new_ls, id_map)
        elif self._dirty or dropped or folded:
            reselected = self._compact_private(alive_base, alive_delta,
                                               new_ls)
        self._reset_staging()
        rec = {"seconds": time.perf_counter() - t0, "folded_rows": folded,
               "dropped_rows": dropped, "n": len(self.base.label_sets),
               "reselected": reselected, "id_map": id_map,
               "arena_version": (self.base.arena.version
                                 if self.base.arena is not None else 0)}
        self.compaction_log.append(rec)
        if _metrics.enabled():
            _M_MUT_ROWS.labels("folded").inc(folded)
            _M_MUT_ROWS.labels("dropped").inc(dropped)
            if reselected:
                _M_RESELECTS.inc()
        self._record_mutation("flush", folded, t0)
        return rec

    def _record_mutation(self, op: str, rows: int, t0: float) -> None:
        """Host-side mutation accounting — one boolean check when
        telemetry is off, plain-Python bookkeeping when on."""
        if _metrics.enabled():
            _M_MUT.labels(op).inc()
            _M_MUT_ROWS.labels(op).inc(rows)
            _M_MUT_S.labels(op).observe(time.perf_counter() - t0)
            dead = int(self._base_dead.sum() + self._delta_dead.sum())
            _M_LIVE.set(self.sentinel - dead)
            _M_TOMB.set(dead)
            _M_DELTA.set(self._n_inserted)

    def _piggyback_selection(self, table: GroupTable) -> EISResult | None:
        """Drift-triggered weighted reselect, evaluated only when a
        compaction is already paying for a rebuild (ISSUE 4 policy)."""
        if (self.monitor is None or self.space_budget is None
                or self.monitor.n_seen < self.min_queries
                or self.monitor.drift() <= self.drift_threshold):
            return None
        sel = weighted_select(table.closure_sizes,
                              self.monitor.distribution(), self.space_budget)
        self.monitor.snapshot()
        return selection_from_weighted(sel)

    def _compact_lazy(self, alive_base, alive_delta, new_ls,
                      id_map) -> bool:
        eng = self.base
        # incremental GroupTable: membership remap + closure arithmetic —
        # no re-grouping pass, no O(Σ 2^|G|) subset re-expansion
        delta_ls_alive = [ls for ls, a in zip(self._delta_ls, alive_delta)
                          if a]
        restricted = self._build_kwargs.get("query_label_sets") is not None
        table = eng.table.compacted(alive_base, delta_ls_alive,
                                    add_new_candidates=not restricted)
        selection = self._piggyback_selection(table)
        reselected = selection is not None
        if selection is None:
            # keep the selected keys, refresh their sizes from the updated
            # closures (empty closures keep their — now empty — segment:
            # exactness of PostFiltering makes that correct, cf. §3.6)
            selected = {key: (table.n if key == EMPTY_KEY
                              else int(table.closure_sizes.get(key, 0)))
                        for key in eng.selection.selected}
            selection = EISResult(
                selected=selected,
                cost=sum(v for kk, v in selected.items() if kk != EMPTY_KEY),
                rounds=list(eng.selection.rounds), c=eng.selection.c,
                assignment=dict(eng.selection.assignment))

        # remap the OLD segments into the new numbering instead of paying
        # closure_members() per selected key: survivors keep stream order,
        # so old member lists filter+shift monotonically, and appended
        # delta rows (ids ≥ #alive base) append in containment order —
        # exactly what the new table's closure_members would return.  The
        # renumbering is _survivors()'s id_map — the ONE definition of it
        n_base = len(eng.label_sets)
        remap = id_map[:n_base]
        delta_new_ids = id_map[n_base:][alive_delta]
        delta_masks = encode_many(delta_ls_alive)
        rows_hint = {}
        for key in selection.selected:
            old = eng.rows.get(key)
            if old is None:
                continue                 # new key (reselect): table path
            r = remap[old]
            r = r[r >= 0]
            if len(delta_ls_alive):
                keym = key_to_mask(key)
                cont = np.all((delta_masks & keym[None, :]) == keym[None, :],
                              axis=1)
                r = np.concatenate([r, delta_new_ids[cont]])
            rows_hint[key] = as_row_ids(r, table.n)

        # fold the arena from the host mirrors (every buffer already lives
        # there) into buffers of the held capacity — the survivors, then
        # zero pad rows — and carry the version forward.  A device-side
        # gather fold would avoid the re-upload (a padded-shape device fold
        # is the recorded TPU follow-up, ROADMAP D7)
        import dataclasses as _dc

        nb, n = int(alive_base.sum()), len(new_ls)
        cap = eng.reserve.hold_arena(n)
        vecs = np.zeros((cap, eng.vectors.shape[1]), np.float32)
        lws = np.zeros((cap, eng.label_words.shape[1]), np.int32)
        np.compress(alive_base, eng.vectors, axis=0, out=vecs[:nb])
        np.compress(alive_base, eng.label_words, axis=0, out=lws[:nb])
        if self._n_inserted:
            vecs[nb:n] = np.concatenate(self._delta_vec_parts)[alive_delta]
            lws[nb:n] = np.concatenate(self._delta_lw_parts)[alive_delta]
        arena = _dc.replace(Arena.from_host(vecs, lws, storage=eng.storage),
                            version=eng.arena.version + 1)
        eng.rebase(vecs[:n], new_ls, table, selection, arena=arena,
                   label_words=lws[:n], rows_hint=rows_hint)
        return reselected

    def _compact_private(self, alive_base, alive_delta, new_ls) -> bool:
        eng = self.base
        dv = (np.concatenate(self._delta_vec_parts)[alive_delta]
              if self._n_inserted else
              np.zeros((0, eng.vectors.shape[1]), np.float32))
        new_vecs = np.concatenate([eng.vectors[alive_base], dv])
        # the fold IS a from-scratch build with the original arguments —
        # the seeded builders make it bit-identical to a rebuilt engine
        self.base = LabelHybridEngine.build(new_vecs, new_ls,
                                            **self._build_kwargs)
        selection = self._piggyback_selection(self.base.table)
        if selection is not None:
            self.base.apply_selection(selection)
            return True
        return False

    def _fold_if_dirty(self) -> None:
        if not self.lazy and self._dirty:
            self.flush()

    # -- search ---------------------------------------------------------------
    def search(self, queries: np.ndarray,
               query_label_sets: Sequence[tuple[int, ...]], k: int,
               **search_params) -> tuple[np.ndarray, np.ndarray]:
        return self.search_batched(queries, query_label_sets, k,
                                   **search_params)

    def search_batched(self, queries: np.ndarray,
                       query_label_sets: Sequence[tuple[int, ...]], k: int,
                       *, min_bucket: int = 1,
                       **search_params) -> tuple[np.ndarray, np.ndarray]:
        """Filtered top-k over the mutated stream — bit-identical (modulo
        the monotonic survivor renumbering) to
        ``LabelHybridEngine.search_batched`` on an engine rebuilt from the
        surviving rows.

        Arena-native: per candidate-span tier (the base executor's
        partition, shared via ``arena_tier_batches``) one tombstone-fused
        segmented launch + one jitted scatter into a query-aligned
        assembly buffer; then ONE delta scan for the whole batch and ONE
        in-program merge; the host synchronizes exactly once at the end.
        Private-storage: pending INSERTS fold (the structures cannot
        absorb them in-place); pending DELETES stay lazy — the engine
        passes per-selected-key tombstone bitmaps down the
        ``search_padded(tomb=…)`` protocol (``_private_tombs``).
        """
        telem = _metrics.enabled() or _trace.enabled()
        clock = StageClock(telem)
        if self.monitor is not None:
            self.monitor.observe([tuple(ls) for ls in query_label_sets])
        if not self.lazy:
            self._fold_if_dirty()
            return self.base.search_batched(queries, query_label_sets, k,
                                            min_bucket=min_bucket,
                                            tomb_by_key=self._private_tombs(),
                                            **search_params)
        if search_params:
            raise TypeError(f"arena-native backend {self.base.backend!r} "
                            f"takes no search params; got "
                            f"{sorted(search_params)}")
        eng = self.base
        with _trace.span("search.route", Q=len(queries),
                         backend=eng.backend):
            queries = np.asarray(queries, dtype=np.float32)
            Q = queries.shape[0]
            n_base = len(eng.label_sets)
            sentinel = check_global_id_contract(self.sentinel)
            out_d = np.full((Q, k), np.inf, dtype=np.float32)
            out_i = np.full((Q, k), sentinel, dtype=np.int32)
            if Q == 0:
                return out_d, out_i

            import jax.numpy as jnp

            from ..index.base import pow2_bucket

            qmasks = encode_many(query_label_sets)
            qwords = masks_to_int32_words(qmasks)
            routed = eng.route_many(query_label_sets, qmasks)
        clock.lap("route")
        seg_before = (_kernel_ops._segmented_topk._cache_size()
                      if telem else None)
        tier_bucket: dict[tuple[int, bool], int] = {}
        delta = self.delta
        # tombstone mask only when base deletes are actually pending: the
        # un-deleted stream then runs the exact static program (zero mask
        # cost); warmup pre-traces both variants so flipping is retrace-free
        tomb = eng.arena.tombstones if self._has_base_tombs else None
        # base results assemble query-aligned into ONE [Q-bucket, k] buffer
        # (a scatter per tier); the delta is scanned ONCE for the whole
        # batch (per-query results are independent of batch composition)
        # and merged in ONE in-program pass — per-tier work stays two
        # device calls, and the host synchronizes exactly once at the end
        qb = pow2_bucket(Q, min_bucket)
        base_v = jnp.full((qb, k), jnp.inf, jnp.float32)
        base_g = jnp.full((qb, k), n_base, jnp.int32)
        for qids, qp, lp, starts, lens, lmax, in_place, g in \
                eng.arena_tier_batches(queries, qwords, routed, min_bucket):
            clock.lap("pack")
            if telem:
                tier_bucket[lmax, in_place] = qp.shape[0]
            with _trace.span("search.launch", lmax=lmax,
                             bucket=qp.shape[0]):
                bvals, _, bgid = _kernel_ops.segmented_topk(
                    qp, lp, eng.arena.vectors, eng.arena.label_words,
                    eng.arena.norms, eng._rows_concat_dev, starts, lens,
                    k=k, lmax=lmax, metric=eng.metric,
                    backend=eng._seg_backend, tomb=tomb,
                    fused=eng._seg_fused, in_place=in_place,
                    **eng.scan_kwargs())
                idx = np.full(bvals.shape[0], qb, np.int32)
                idx[:g] = qids                  # pad lanes scatter out of
                base_v, base_g = _kernel_ops.scatter_topk_rows(
                    base_v, base_g, jnp.asarray(idx), bvals, bgid)
            clock.lap("launch")
        clock.lap("pack")   # the partition's last step
        if delta.count:
            with _trace.span("search.launch", delta=delta.count):
                qp_all = np.zeros((qb, queries.shape[1]), np.float32)
                qp_all[:Q] = queries
                lp_all = np.zeros((qb, qwords.shape[1]), np.int32)
                lp_all[:Q] = qwords
                dvals, dslot = _kernel_ops.delta_topk(
                    qp_all, lp_all, delta.vectors, delta.label_words,
                    delta.norms, delta.tombstones, delta.count, k=k,
                    metric=eng.metric, backend=eng._seg_backend,
                    fused=eng._seg_fused, **delta.tier_kwargs())
                base_v, base_g = _kernel_ops.merge_topk(
                    base_v, base_g, dvals, dslot, n_base, sentinel, k=k)
            clock.lap("launch")
        # empty delta: base_g's empty-slot id n_base IS the stream sentinel
        with _trace.span("search.sync"):
            out_d[:] = np.asarray(base_v)[:Q]
            out_i[:] = np.asarray(base_g)[:Q]
        clock.lap("sync")
        if telem:
            dead = int(self._base_dead.sum() + self._delta_dead.sum())
            with _trace.span("search.telemetry"):
                record_search_telemetry(
                    eng, routed, qmasks, k, Q, clock,
                    seg_before=seg_before, tier_bucket=tier_bucket,
                    min_bucket=min_bucket,
                    tomb_density=dead / max(1, self.sentinel))
        return out_d, out_i

    # -- warmup ---------------------------------------------------------------
    def warmup(self, ks: Sequence[int], buckets: Sequence[int],
               **search_params) -> dict:
        """Pre-trace the streaming dispatch tables (ISSUE 4 satellite):
        the tombstone-fused base program per (k, Q-bucket, span tier), the
        delta scan per (k, Q-bucket, current capacity tier), and the merge
        per (k, Q-bucket) — so the first post-insert batch pays no retrace
        (measured with compile caches cleared in exp10, the exp9 pattern).
        Private-storage backends fold pending inserts and delegate to the
        base warmup, tracing each index's tombstone-masked variant too
        when lazy deletes are active (first post-delete batch: no
        retrace)."""
        if not self.lazy:
            self._fold_if_dirty()
            return self.base.warmup(ks, buckets,
                                    tomb_variants=self.lazy_deletes_active,
                                    **search_params)
        import jax
        import jax.numpy as jnp

        from ..index.base import pow2_bucket

        t0 = time.perf_counter()
        eng, delta = self.base, self.delta
        D = eng.vectors.shape[1]
        W = eng.label_words.shape[1]
        span_tiers = eng.span_tiers()
        outs: list[object] = []
        for k in ks:
            for b in buckets:
                bucket = pow2_bucket(b)
                qz = np.zeros((bucket, D), np.float32)
                lz = np.zeros((bucket, W), np.int32)
                zero = jnp.zeros(bucket, jnp.int32)
                dvals, dslot = _kernel_ops.delta_topk(
                    qz, lz, delta.vectors, delta.label_words, delta.norms,
                    delta.tombstones, delta.count, k=k, metric=eng.metric,
                    backend=eng._seg_backend, fused=eng._seg_fused,
                    **delta.tier_kwargs())
                outs.append(dvals)
                for lmax, in_place in span_tiers:
                    # both tombstone variants: the executor flips between
                    # them as deletes arrive / compactions clear them
                    for tomb in (None, eng.arena.tombstones):
                        bvals, _, bgid = _kernel_ops.segmented_topk(
                            qz, lz, eng.arena.vectors,
                            eng.arena.label_words, eng.arena.norms,
                            eng._rows_concat_dev, zero, zero,
                            k=k, lmax=lmax, metric=eng.metric,
                            backend=eng._seg_backend, tomb=tomb,
                            fused=eng._seg_fused, in_place=in_place,
                            **eng.scan_kwargs())
                        outs.append(bvals)
                mv, _ = _kernel_ops.merge_topk(
                    bvals, bgid, dvals, dslot, len(eng.label_sets),
                    self.sentinel, k=k)
                outs.append(mv)
                # the assembly scatter of each tier bucket a batch of this
                # bucket can hold: a tier's group is any part of the batch
                for tb in sorted({pow2_bucket(t) for t in buckets}):
                    if tb <= bucket:
                        sv, _ = _kernel_ops.scatter_topk_rows(
                            jnp.full((bucket, k), jnp.inf, jnp.float32),
                            jnp.full((bucket, k), 0, jnp.int32),
                            jnp.zeros(tb, jnp.int32),
                            jnp.full((tb, k), jnp.inf, jnp.float32),
                            jnp.zeros((tb, k), jnp.int32))
                        outs.append(sv)
        for o in outs:
            jax.block_until_ready(jnp.asarray(o))
        return {"seconds": time.perf_counter() - t0, "programs": len(outs)}

    def warmup_serving(self, ks: Sequence[int], min_bucket: int,
                       max_batch: int, *, delta_rows_hint: int | None = None,
                       **search_params) -> dict:
        """Serving-shaped warmup with mutations in-flight: the full
        power-of-two Q-bucket ladder a micro-batcher can emit
        (``index.base.serving_buckets``), PLUS — on arena-native backends —
        the delta-scan program for every capacity tier the delta can grow
        through before the fill trigger compacts it.  The delta scan is
        keyed on its capacity tier (``delta_topk`` traces per (k, Q-bucket,
        capacity)), so without this a mid-serve insert that doubles the
        delta would pay a fresh trace on the very next search — the one
        latency spike warmup exists to remove.

        ``delta_rows_hint``: expected delta occupancy before the next
        flush; defaults to the ``max_delta_fraction`` trigger point (the
        most the delta can hold), or just the current tier when the
        trigger is disabled."""
        from ..index.base import DeltaArena, pow2_bucket, serving_buckets

        buckets = serving_buckets(min_bucket, max_batch)
        out = self.warmup(ks, buckets, **search_params)
        if not self.lazy:
            return out
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        eng = self.base
        if delta_rows_hint is None:
            delta_rows_hint = (
                int(self.max_delta_fraction * max(1, len(eng.label_sets)))
                if self.max_delta_fraction is not None else 0)
        D = eng.vectors.shape[1]
        W = eng.label_words.shape[1]
        cap = self.delta.capacity
        top = pow2_bucket(max(delta_rows_hint, cap))
        outs: list[object] = []
        c = cap * 2
        while c <= top:
            dummy = DeltaArena.empty(D, W, c, storage=eng.storage)
            for k in ks:
                for b in buckets:
                    qz = np.zeros((b, D), np.float32)
                    lz = np.zeros((b, W), np.int32)
                    dvals, _ = _kernel_ops.delta_topk(
                        qz, lz, dummy.vectors, dummy.label_words,
                        dummy.norms, dummy.tombstones, dummy.count, k=k,
                        metric=eng.metric, backend=eng._seg_backend,
                        fused=eng._seg_fused, **dummy.tier_kwargs())
                    outs.append(dvals)
            c *= 2
        for o in outs:
            jax.block_until_ready(jnp.asarray(o))
        out["seconds"] += time.perf_counter() - t0
        out["programs"] += len(outs)
        return out

    # -- durability hooks (core/durability.py; DESIGN.md §5) ------------------
    def staged_state(self) -> dict:
        """The host-side mutation staging a snapshot must persist — every
        pending insert/delete since the last compaction, with the original
        append batching preserved (``part_lens``) so a restore replays the
        exact power-of-two growth sequence the delta arena went through
        (byte-identical device buffers, not just equal live rows)."""
        return {
            "base_dead": self._base_dead.copy(),
            "delta_dead": self._delta_dead.copy(),
            "delta_vectors": (np.concatenate(self._delta_vec_parts)
                              if self._delta_vec_parts else
                              np.zeros((0, self.base.vectors.shape[1]),
                                       np.float32)),
            "part_lens": np.asarray(
                [len(p) for p in self._delta_vec_parts], np.int64),
            "delta_ls": list(self._delta_ls),
            "n_inserted": self._n_inserted,
            "dirty": self._dirty,
            "has_base_tombs": self._has_base_tombs,
        }

    def restore_staged_state(self, state: dict) -> None:
        """Inverse of :meth:`staged_state` on a freshly-built engine:
        re-stage the pending mutations WITHOUT re-running compaction
        triggers (the snapshot captured post-trigger state — replaying
        triggers here would fold what the survivor engine had pending)."""
        self._reset_staging()
        ls = [tuple(s) for s in state["delta_ls"]]
        vecs = np.ascontiguousarray(state["delta_vectors"], np.float32)
        off = 0
        for n in np.asarray(state["part_lens"], np.int64):
            part = vecs[off:off + int(n)]
            lw = masks_to_int32_words(encode_many(ls[off:off + int(n)]))
            self._delta_vec_parts.append(part)
            self._delta_lw_parts.append(lw)
            if self.lazy:
                self.delta = self.delta.appended(part, lw)
            off += int(n)
        self._delta_ls = ls
        self._n_inserted = int(state["n_inserted"])
        self._base_dead = np.asarray(state["base_dead"], bool).copy()
        self._delta_dead = np.asarray(state["delta_dead"], bool).copy()
        self._dirty = bool(state["dirty"])
        self._has_base_tombs = bool(state["has_base_tombs"])
        if self.lazy:
            if self._has_base_tombs:
                self.base.arena = self.base.arena.with_tombstones(
                    self._base_dead)
            if self._delta_dead.any():
                self.delta = self.delta.with_tombstones(self._delta_dead)

    # -- reporting ------------------------------------------------------------
    def stats(self):
        """Base-engine stats with the streaming surface filled in
        (ISSUE 4 satellite): ``live_rows`` / ``tombstoned_rows`` /
        ``delta_rows`` / ``arena_version`` / ``delta_nbytes``; ``nbytes``
        additionally counts the delta arena."""
        import dataclasses as _dc

        st = self.base.stats()
        dead = int(self._base_dead.sum() + self._delta_dead.sum())
        delta_nbytes = self.delta.nbytes if self.delta is not None else 0
        dt = (self.delta.tier_nbytes if self.delta is not None
              else {"codes": 0, "scales": 0, "rerank": 0, "tombstone": 0})
        st = _dc.replace(
            st,
            live_rows=self.sentinel - dead,
            tombstoned_rows=dead,
            delta_rows=self._n_inserted,
            arena_version=(self.base.arena.version
                           if self.base.arena is not None else 0),
            delta_nbytes=delta_nbytes,
            nbytes=st.nbytes + delta_nbytes,
            # per-tier split covers base + delta (the same representation
            # lives in both, DESIGN.md §3.8)
            codes_nbytes=st.codes_nbytes + dt["codes"],
            scales_nbytes=st.scales_nbytes + dt["scales"],
            rerank_nbytes=st.rerank_nbytes + dt["rerank"],
            tombstone_nbytes=st.tombstone_nbytes + dt["tombstone"],
        )
        publish_engine_gauges(st)
        return st
