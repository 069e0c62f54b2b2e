"""Held device shapes of the streaming engine (DESIGN.md §3.6): the arena's
rows, the row table's length and each key's span tier are shapes of every
compiled scan program, and a compaction with freshly labelled rows moves
all three.  ``StreamingEngine`` holds them (``core.engine.ShapeReserve``):

  * results stay bit-identical to an engine rebuilt from scratch on the
    surviving rows — the unpadded, untiered path — through a sequence of
    compactions, with a span tier held above a shrunken segment's own;
  * after ``warmup_serving`` a sliding window through six compactions
    compiles no scan, merge or assembly program;
  * a held buffer grows by its headroom when outgrown and never shrinks;
  * the mutation path's spans (``stream.insert``, ``stream.delete``,
    ``stream.compact``, ``stream.delta_append``, ``stream.tomb_upload``)
    nest as the calls do and open a profiler annotation while tracing.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import (LabelHybridEngine, LabelWorkloadConfig,
                        StreamingEngine, generate_label_sets,
                        generate_query_label_sets)
from repro.core.engine import ShapeReserve, held_capacity
from repro.index.base import pow2_bucket
from repro.kernels import ops
from repro.obs import trace

K = 5


def _rows(n, d, seed, label_seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    ls = generate_label_sets(n, LabelWorkloadConfig(num_labels=8,
                                                    seed=label_seed))
    return x, ls


def _rebuilt(se: StreamingEngine):
    """An engine built from scratch on the surviving rows (stream order)
    and the stream id of each of its rows."""
    alive_base, alive_delta = ~se._base_dead, ~se._delta_dead
    n_base = len(se.base.label_sets)
    parts = [se.base.vectors[alive_base]]
    if se._n_inserted:
        parts.append(np.concatenate(se._delta_vec_parts)[alive_delta])
    surv_ls = ([s for s, a in zip(se.base.label_sets, alive_base) if a]
               + [s for s, a in zip(se._delta_ls, alive_delta) if a])
    eng = LabelHybridEngine.build(np.concatenate(parts), surv_ls,
                                  mode="eis", c=0.2, backend="flat")
    ids = np.concatenate([np.flatnonzero(alive_base),
                          n_base + np.flatnonzero(alive_delta)])
    return eng, ids


def _assert_matches_rebuilt(se: StreamingEngine, qv, qls, tag: str):
    oracle, ids = _rebuilt(se)
    d_s, i_s = se.search_batched(qv, qls, K)
    d_o, i_o = oracle.search_batched(qv, qls, K)
    i_o = np.where(i_o < ids.size, ids[np.clip(i_o, 0, ids.size - 1)],
                   se.sentinel).astype(np.int32)
    np.testing.assert_array_equal(i_s, i_o, err_msg=f"{tag}: ids")
    np.testing.assert_array_equal(d_s, d_o, err_msg=f"{tag}: dists")


def _slide(se: StreamingEngine, oldest: np.ndarray, x, ls) -> np.ndarray:
    """One step of a sliding window: insert ``x``, then delete as many of
    the oldest live rows; ``oldest`` holds the live ids, oldest first, and
    follows each compaction's renumbering."""
    n_log = len(se.compaction_log)
    ids = se.insert(x, ls)
    for rec in se.compaction_log[n_log:]:
        oldest = rec["id_map"][oldest]
        oldest = oldest[oldest >= 0]
    se.delete(oldest[:len(ids)])
    return np.concatenate([oldest[len(ids):], ids])


def _queries(ls, q, seed):
    qv = np.random.default_rng(seed).standard_normal(
        (q, 16)).astype(np.float32)
    qls = generate_query_label_sets(ls, q - 1, seed=seed)
    return qv, qls + [(11,)]          # a label no row carries: all empty


def test_held_shapes_match_rebuilt_engine_through_compactions():
    n, d, m = 3000, 16, 30
    x, ls = _rows(n, d, 1, 3)
    new_x, new_ls = _rows(12 * m, d, 2, 21)   # freshly labelled stream
    se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                               max_delta_fraction=0.02,
                               max_tombstone_fraction=None)
    eng = se.base
    arena_rows = eng.arena.n
    table_len = eng._rows_concat_dev.shape[0]
    assert arena_rows > n and table_len > eng.rows_concat.size
    lengths = {eng.rows_concat.size}
    oldest = np.arange(n)
    for step in range(12):
        oldest = _slide(se, oldest, new_x[step * m:(step + 1) * m],
                        new_ls[step * m:(step + 1) * m])
        qv, qls = _queries(ls, 24, 100 + step)
        _assert_matches_rebuilt(se, qv, qls, f"step {step}")
        eng = se.base
        lengths.add(eng.rows_concat.size)
        # the shapes held: arena rows, row table length; each key's tier
        # holds its segment
        assert eng.arena.n == arena_rows
        assert eng._rows_concat_dev.shape[0] == table_len
        for key, (_, length) in eng.segments.items():
            assert eng.seg_tier[key] == eng.reserve.tiers[key]
            assert eng.seg_tier[key] >= pow2_bucket(length)
    assert len(se.compaction_log) >= 5
    assert len(lengths) > 1           # the fresh labels moved the table


def test_a_shrunken_segment_keeps_its_span_tier():
    n, d = 3000, 16
    x, ls = _rows(n, d, 4, 3)
    se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                               max_delta_fraction=None,
                               max_tombstone_fraction=None)
    eng = se.base
    key = max((k for k in eng.segments if k not in eng.in_place_keys),
              key=lambda k: eng.segments[k][1])
    tier = eng.seg_tier[key]
    members = eng.rows[key]
    se.delete(members[: int(0.8 * members.size)])
    qv, qls = _queries(ls, 24, 7)
    qls[:8] = [key] * 8               # queries routed to the segment
    _assert_matches_rebuilt(se, qv, qls, "pending")
    se.flush()
    eng = se.base
    length = eng.segments[key][1]
    assert pow2_bucket(length) < tier == eng.seg_tier[key]
    assert (tier, False) in eng.span_tiers()
    _assert_matches_rebuilt(se, qv, qls, "flushed")


def _caches():
    return (ops._segmented_topk._cache_size(), ops._merge_topk._cache_size(),
            ops.scatter_topk_rows._cache_size())


def test_sliding_window_compiles_nothing_after_warmup():
    n, d, m, q = 20_000, 8, 200, 16
    x, ls = _rows(n, d, 5, 3)
    new_x, new_ls = _rows(14 * m, d, 6, 21)
    se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                               max_delta_fraction=0.02,
                               max_tombstone_fraction=None)
    se.warmup_serving([K], min_bucket=1, max_batch=q)
    before = _caches()
    oldest = np.arange(n)
    rng = np.random.default_rng(8)
    for step in range(14):    # a compaction every second step from the 3rd
        oldest = _slide(se, oldest, new_x[step * m:(step + 1) * m],
                        new_ls[step * m:(step + 1) * m])
        qv = rng.standard_normal((q, d)).astype(np.float32)
        qls = generate_query_label_sets(ls, q, seed=200 + step)
        se.search_batched(qv, qls, K)
    assert len(se.compaction_log) >= 6
    assert _caches() == before


def test_held_capacity_grows_by_its_headroom_and_never_shrinks():
    h = 1 / 64
    assert held_capacity(0, 1000, h) == 1016      # 1015.6, whole bytes
    assert held_capacity(1016, 1016, h) == 1016   # held while it fits
    assert held_capacity(1016, 10, h) == 1016     # never shrinks
    assert held_capacity(1016, 1017, h) == 1040   # outgrown: 1033 -> 1040
    assert held_capacity(0, 0, h) == 8


def test_row_table_and_arena_grow_when_outgrown_and_never_shrink():
    n, d = 2000, 16
    x, ls = _rows(n, d, 9, 3)
    se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                               max_delta_fraction=None,
                               max_tombstone_fraction=None)
    res = se.base.reserve
    rows0, arena0 = res.rows, res.arena
    assert arena0 == se.base.arena.n == held_capacity(
        0, n, ShapeReserve.ARENA_HEADROOM)
    # rows carrying every label land in every segment: the table and the
    # arena are outgrown at the next compaction
    rng = np.random.default_rng(10)
    se.insert(rng.standard_normal((600, d)).astype(np.float32),
              [tuple(range(8))] * 600)
    se.flush()
    eng = se.base
    assert eng.rows_concat.size > rows0 and len(eng.label_sets) > arena0
    assert res.rows == held_capacity(rows0, eng.rows_concat.size,
                                     ShapeReserve.ROW_HEADROOM)
    assert res.arena == held_capacity(arena0, len(eng.label_sets),
                                      ShapeReserve.ARENA_HEADROOM)
    assert eng._rows_concat_dev.shape[0] == res.rows
    assert eng.arena.n == res.arena
    qv, qls = _queries(ls, 24, 11)
    _assert_matches_rebuilt(se, qv, qls, "grown")
    grown_rows, grown_arena = res.rows, res.arena
    se.delete(np.arange(0, len(eng.label_sets), 2))
    se.flush()
    assert (res.rows, res.arena) == (grown_rows, grown_arena)
    assert se.base._rows_concat_dev.shape[0] == grown_rows
    assert se.base.arena.n == grown_arena
    _assert_matches_rebuilt(se, qv, qls, "shrunk")


def test_static_engine_holds_no_shape():
    x, ls = _rows(1500, 16, 12, 3)
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    assert eng.reserve is None
    assert eng.arena.n == 1500
    assert eng._rows_concat_dev.shape[0] == eng.rows_concat.size
    assert "n_rows" not in eng.scan_kwargs()
    assert eng.seg_tier == {key: pow2_bucket(length)
                            for key, (_, length) in eng.segments.items()}


MUTATION_SPANS = ("stream.insert", "stream.delete", "stream.compact",
                  "stream.delta_append", "stream.tomb_upload")


def test_mutation_spans_nest_and_open_annotations(monkeypatch):
    built = []

    class Recording:
        def __init__(self, name):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    x, ls = _rows(1000, 16, 13, 3)
    se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                               max_delta_fraction=0.05,
                               max_tombstone_fraction=None)
    rng = np.random.default_rng(14)
    trace.enable()
    trace.reset()
    try:
        se.insert(rng.standard_normal((40, 16)).astype(np.float32),
                  [(0,)] * 40)
        se.delete([1, 2, 3])
        # 40 + 40 > 5% of 1000: this insert compacts first
        se.insert(rng.standard_normal((40, 16)).astype(np.float32),
                  [(1,)] * 40)
    finally:
        trace.disable()
    events = trace.get_tracer().events
    names = [e["name"] for e in events]
    assert set(names) == set(MUTATION_SPANS)
    assert "stream.flush" not in names
    assert sorted(built) == sorted(names)       # one annotation per span

    def inside(inner, outer):
        return (outer["ts"] <= inner["ts"]
                and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])

    inserts = [e for e in events if e["name"] == "stream.insert"]
    deletes = [e for e in events if e["name"] == "stream.delete"]
    compact, = [e for e in events if e["name"] == "stream.compact"]
    appends = [e for e in events if e["name"] == "stream.delta_append"]
    upload, = [e for e in events if e["name"] == "stream.tomb_upload"]
    assert len(inserts) == len(appends) == 2 and len(deletes) == 1
    assert inside(compact, inserts[1]) and not inside(compact, inserts[0])
    assert all(inside(a, i) for a, i in zip(appends, inserts))
    assert inside(upload, deletes[0])
    # the compaction runs before the insert's append
    assert compact["ts"] + compact["dur"] <= appends[1]["ts"]
