"""Arena-backed shared storage + segmented executor (ISSUE 3 tentpole).

Pins the three tentpole guarantees:

  1. **memory** — engine device storage on an arena-native backend is the
     shared arena (uploaded once) plus the int32 CSR segment table: ≤
     N·D·4 + Σ|I|·4 + constants (label words + norms), NOT Σ|I|·(D+W)·4
     duplicated per selected index;
  2. **kernel** — the chunked segmented program is bit-identical to the
     unchunked ``ref.segmented_filtered_topk`` oracle, on tie-heavy
     integer data, across chunk sizes (the merge-invariant proof);
  3. **dispatch** — ``engine.warmup`` pre-traces every (k, bucket,
     span-tier) program, and the sentinel/dtype contract
     (``index.base.check_global_id_contract``) is enforced centrally.

Bit-parity of the segmented executor vs ``search_looped`` on every backend
is pinned by tests/test_search_padded_parity.py.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (LabelHybridEngine, LabelWorkloadConfig,
                        generate_label_sets, generate_query_label_sets)
from repro.core.engine import held_capacity
from repro.core.labels import encode_many, masks_to_int32_words
from repro.index.base import (ROW_ID_DTYPE, as_row_ids,
                              check_global_id_contract, pow2_bucket)
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def fix():
    rng = np.random.default_rng(21)
    N, D, Q = 3000, 32, 96
    x = rng.standard_normal((N, D)).astype(np.float32)
    ls = generate_label_sets(N, LabelWorkloadConfig(num_labels=10, seed=13))
    qv = rng.standard_normal((Q, D)).astype(np.float32)
    qls = generate_query_label_sets(ls, Q, seed=14, from_base_fraction=0.75)
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    return dict(x=x, ls=ls, qv=qv, qls=qls, eng=eng, N=N, D=D)


# ---------------------------------------------------------------------------
# 1. shared storage
# ---------------------------------------------------------------------------

def test_arena_memory_bound(fix):
    """ISSUE 3 acceptance (extended by ISSUE 4): device memory ≤
    N·(D+W+1)·4 + Σ|I|·4 + ⌈N/8⌉ — vectors, label words, norms, the CSR
    segment table, and the streaming tombstone bitmap the arena now always
    carries.  The pre-arena engine stored Σ|I|·(D·4 + W·4) — a ~Σ|I|/N ≈
    1/c duplication factor."""
    eng, N, D = fix["eng"], fix["N"], fix["D"]
    st = eng.stats()
    W = eng.label_words.shape[1]
    sum_i = st.total_entries
    bound = N * (D + W + 1) * 4 + sum_i * 4 + -(-N // 8)
    assert st.nbytes <= bound, (st.nbytes, bound)
    # and the old duplicated scheme would have blown past it
    old = sum_i * (D * 4 + W * 4)
    assert st.nbytes < old, (st.nbytes, old)
    assert st.arena_nbytes == N * (D + W + 1) * 4 + -(-N // 8)
    assert st.segment_nbytes == sum_i * 4
    # static engine: streaming surface is quiescent
    assert (st.live_rows, st.tombstoned_rows, st.delta_rows) == (N, 0, 0)
    assert st.arena_version == 0 and st.delta_nbytes == 0


def _held(se, n_rows: int, entries: int) -> tuple[int, int]:
    """The streaming engine's held arena rows and row-table length
    (``ShapeReserve``): at least what they hold, at most that plus their
    headroom, rounded up to a byte of tombstone bits."""
    from repro.core.engine import ShapeReserve
    n_held, r_held = se.base.arena.n, se.base.reserve.rows
    assert n_held == se.base.reserve.arena
    assert n_held == held_capacity(0, n_rows, ShapeReserve.ARENA_HEADROOM)
    assert n_rows <= n_held <= n_rows * (1 + ShapeReserve.ARENA_HEADROOM) + 8
    assert r_held == held_capacity(0, entries, ShapeReserve.ROW_HEADROOM)
    assert entries <= r_held <= entries * (1 + ShapeReserve.ROW_HEADROOM) + 8
    return n_held, r_held


def test_streaming_memory_bound(fix):
    """ISSUE 4 satellite: with the delta arena and tombstone bitmaps the
    device bound extends to

        N'·(D+W+1)·4 + ⌈N'/8⌉  +  R'·4  +  cap·(D+W+1)·4 + ⌈cap/8⌉

    (base arena + its bitmap, CSR segment table, delta arena at its
    current capacity tier + its bitmap), where the arena's rows N' and
    the table's length R' are the capacities the engine holds across
    compactions: N and Σ|I| plus their headroom."""
    from repro.core import StreamingEngine

    N, D = fix["N"], fix["D"]
    # fresh engine: wrapping would tombstone the module-shared arena
    se = StreamingEngine.build(fix["x"], fix["ls"], mode="eis", c=0.2,
                               backend="flat", max_delta_fraction=None,
                               max_tombstone_fraction=None)
    rng = np.random.default_rng(6)
    se.insert(rng.standard_normal((100, D)).astype(np.float32),
              [(0,)] * 100)
    se.delete([0, 1, 2])
    st = se.stats()
    W = se.base.label_words.shape[1]
    cap = se.delta.capacity
    assert cap == 256                      # 100 rows sit in the first tier
    n_held, r_held = _held(se, N, st.total_entries)
    bound = (n_held * (D + W + 1) * 4 + -(-n_held // 8)
             + r_held * 4
             + cap * (D + W + 1) * 4 + -(-cap // 8))
    assert st.nbytes <= bound, (st.nbytes, bound)
    assert st.delta_nbytes == cap * (D + W + 1) * 4 + -(-cap // 8)
    # the bound holds across a capacity-tier growth too
    se.insert(rng.standard_normal((300, D)).astype(np.float32),
              [(1,)] * 300)
    st2 = se.stats()
    cap2 = se.delta.capacity
    # 300 rows pad to a 512 batch tier appended at cursor 100 → tier 1024
    assert cap2 == 1024
    bound2 = (n_held * (D + W + 1) * 4 + -(-n_held // 8) + r_held * 4
              + cap2 * (D + W + 1) * 4 + -(-cap2 // 8))
    assert st2.nbytes <= bound2, (st2.nbytes, bound2)


# -- tiered-precision byte accounting (DESIGN.md §3.8) ----------------------
# closed-form per-tier bytes for n rows at dim d / w label words:
#   codes      n·d·itemsize(dtype)
#   labels     n·w·4          norms   n·4         tombstone  ⌈n/8⌉
#   scales     2·n·4 (int8 scale + zero-point columns, else 0)
#   rerank     n·(d+1)·4 (exact f32 rows + their norms, else 0)

_TIER_ITEM = {"f32": 4, "fp16": 2, "int8": 1}


def _tier_bytes(n: int, d: int, w: int, storage: str) -> dict:
    from repro.index.base import parse_storage
    dtype, has_rerank = parse_storage(storage)
    return dict(codes=n * d * _TIER_ITEM[dtype], labels=n * w * 4,
                norms=n * 4, scales=(2 * n * 4 if dtype == "int8" else 0),
                rerank=(n * (d + 1) * 4 if has_rerank else 0),
                tombstone=-(-n // 8))


@pytest.mark.parametrize("storage", ["f32", "fp16", "int8",
                                     "fp16+rerank", "int8+rerank"])
def test_arena_memory_bound_per_dtype(fix, storage):
    """ISSUE 6 satellite: the per-dtype closed-form arena bound, and the
    EngineStats per-tier split summing back to arena_nbytes exactly."""
    N, D = 800, fix["D"]
    eng = LabelHybridEngine.build(fix["x"][:N], fix["ls"][:N], mode="eis",
                                  c=0.2, backend="flat", storage=storage)
    st = eng.stats()
    W = eng.label_words.shape[1]
    t = _tier_bytes(N, D, W, storage)
    assert st.storage == storage
    assert st.codes_nbytes == t["codes"]
    assert st.scales_nbytes == t["scales"]
    assert st.rerank_nbytes == t["rerank"]
    assert st.tombstone_nbytes == t["tombstone"]
    assert st.arena_nbytes == sum(t.values())
    assert eng.arena.tier_nbytes == t
    assert st.nbytes == st.arena_nbytes + st.segment_nbytes
    # the compressed scan tier must actually shrink the vector bytes
    if storage in ("fp16", "int8"):
        f32_rows = N * D * 4
        assert st.codes_nbytes + st.scales_nbytes < f32_rows


@pytest.mark.parametrize("storage", ["f32", "int8", "int8+rerank"])
def test_streaming_memory_bound_per_dtype(fix, storage):
    """The delta arena holds the SAME tiers as the base: the streaming
    bound extends per dtype with the delta's capacity-tier closed form,
    and the streaming stats' per-tier split covers base + delta."""
    from repro.core import StreamingEngine

    N, D = 800, fix["D"]
    se = StreamingEngine.build(fix["x"][:N], fix["ls"][:N], mode="eis",
                               c=0.2, backend="flat", storage=storage,
                               max_delta_fraction=None,
                               max_tombstone_fraction=None)
    rng = np.random.default_rng(6)
    se.insert(rng.standard_normal((100, D)).astype(np.float32), [(0,)] * 100)
    se.delete([0, 1, 2])
    st = se.stats()
    W = se.base.label_words.shape[1]
    cap = se.delta.capacity
    assert cap == 256
    n_held, _ = _held(se, N, st.total_entries)
    tb = _tier_bytes(n_held, D, W, storage)
    td = _tier_bytes(cap, D, W, storage)
    assert st.delta_nbytes == sum(td.values())
    assert st.codes_nbytes == tb["codes"] + td["codes"]
    assert st.scales_nbytes == tb["scales"] + td["scales"]
    assert st.rerank_nbytes == tb["rerank"] + td["rerank"]
    assert st.tombstone_nbytes == tb["tombstone"] + td["tombstone"]
    assert st.nbytes == (st.arena_nbytes + st.segment_nbytes
                         + st.delta_nbytes)
    assert se.delta.tier_nbytes == td


def test_views_share_one_arena_and_own_nothing(fix):
    eng = fix["eng"]
    arenas = {id(ix.arena) for ix in eng.indexes.values()}
    assert arenas == {id(eng.arena)}            # ONE upload, many views
    assert all(ix.nbytes == 0 for ix in eng.indexes.values())
    # segment table is consistent CSR over the per-key row lists
    off = 0
    for key, rows in eng.rows.items():
        start, length = eng.segments[key]
        assert (start, length) == (off, rows.size)
        np.testing.assert_array_equal(
            eng.rows_concat[start:start + length], rows)
        off += length
    assert off == eng.rows_concat.size


def test_view_protocol_matches_materialized_flat(fix):
    """A view must satisfy the VectorIndex protocol: LOCAL ids, sentinel ==
    num_vectors, same result sets as a materialized FlatIndex on the same
    rows (values allclose — the arena gather uses a different but fixed
    f32 accumulation order than the matmul scan)."""
    from repro.index import FlatIndex

    eng = fix["eng"]
    key = max(eng.segments, key=lambda kk: (eng.segments[kk][1]
                                            if eng.segments[kk][1] < fix["N"]
                                            else 0))
    view = eng.indexes[key]
    rows = eng.rows[key]
    flat = FlatIndex(fix["x"][rows], eng.label_words[rows])
    qw = masks_to_int32_words(encode_many(fix["qls"]))[:8]
    dv, iv = view.search(fix["qv"][:8], qw, 5)
    df, if_ = flat.search(fix["qv"][:8], qw, 5)
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(if_))
    np.testing.assert_allclose(np.asarray(dv), np.asarray(df),
                               rtol=1e-5, atol=1e-4)
    assert view.num_vectors == rows.size


# ---------------------------------------------------------------------------
# 2. kernel: chunked program == unchunked oracle (bitwise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", (1, 5, 17))
@pytest.mark.parametrize("chunk", (64, 256, 512))
def test_segmented_chunked_matches_oracle_bitwise(k, chunk):
    """Tie-heavy integer data: every f32 op is exact, so any deviation in
    the chunked merge's (distance, position) tie-break chain shows up as a
    hard mismatch rather than a tolerance flake."""
    rng = np.random.default_rng(31)
    N, D, Q, lmax = 500, 8, 24, 512
    x = rng.integers(-3, 4, (N, D)).astype(np.float32)
    ls = generate_label_sets(N, LabelWorkloadConfig(num_labels=8, seed=17))
    lx = masks_to_int32_words(encode_many(ls))
    qv = rng.integers(-3, 4, (Q, D)).astype(np.float32)
    qls = generate_query_label_sets(ls, Q - 1, seed=18,
                                    from_base_fraction=0.7)
    qls += [tuple(range(9))]    # impossible combo: empty result row
    lq = masks_to_int32_words(encode_many(qls))
    ax, alw = jnp.asarray(x), jnp.asarray(lx)
    axn = jnp.sum(ax * ax, axis=1)
    parts, starts, lens, off = [], [], [], 0
    for qi in range(Q):
        L = int(rng.integers(1, 500)) if qi else 1   # incl. a size-1 segment
        seg = np.sort(rng.choice(N, L, replace=False)).astype(np.int32)
        parts.append(seg), starts.append(off), lens.append(L)
        off += L
    rows_concat = jnp.asarray(np.concatenate(parts))
    starts = np.asarray(starts, np.int32)
    lens = np.asarray(lens, np.int32)

    wv, wp = ref.segmented_filtered_topk(
        jnp.asarray(qv), jnp.asarray(lq), ax, alw, axn, rows_concat,
        jnp.asarray(starts), jnp.asarray(lens), k, lmax, "l2")
    gv, gp, gg = ops.segmented_topk(qv, lq, ax, alw, axn, rows_concat,
                                    starts, lens, k=k, lmax=lmax,
                                    metric="l2", backend="ref", chunk=chunk)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))
    # global ids resolved in-program: sentinel N on empty, else the
    # segment-table row at the selected position
    gg, gp_np = np.asarray(gg), np.asarray(gp)
    rc = np.asarray(rows_concat)
    for qi in range(Q):
        for j in range(k):
            if gp_np[qi, j] == lmax:
                assert gg[qi, j] == N
            else:
                assert gg[qi, j] == rc[starts[qi] + gp_np[qi, j]]


def test_segmented_pallas_interpret_matches_ref():
    """The scalar-prefetch gather kernel (interpret mode on CPU) agrees
    with the ref path: same finite mask, same positions, allclose values
    (the kernel computes (q-x)² — a different but valid f32 association)."""
    rng = np.random.default_rng(41)
    N, D, Q, lmax = 200, 16, 6, 128
    x = rng.standard_normal((N, D)).astype(np.float32)
    ls = generate_label_sets(N, LabelWorkloadConfig(num_labels=6, seed=5))
    lx = masks_to_int32_words(encode_many(ls))
    qv = rng.standard_normal((Q, D)).astype(np.float32)
    qls = generate_query_label_sets(ls, Q, seed=6)
    lq = masks_to_int32_words(encode_many(qls))
    ax, alw = jnp.asarray(x), jnp.asarray(lx)
    axn = jnp.sum(ax * ax, axis=1)
    parts, starts, lens, off = [], [], [], 0
    for qi in range(Q):
        L = int(rng.integers(1, 120))
        parts.append(np.sort(rng.choice(N, L, replace=False)).astype(np.int32))
        starts.append(off), lens.append(L)
        off += L
    rows_concat = jnp.asarray(np.concatenate(parts))
    starts, lens = np.asarray(starts, np.int32), np.asarray(lens, np.int32)
    args = (qv, lq, ax, alw, axn, rows_concat, starts, lens)
    wv, wp, _ = ops.segmented_topk(*args, k=5, lmax=lmax, metric="l2",
                                   backend="ref")
    gv, gp, _ = ops.segmented_topk(*args, k=5, lmax=lmax, metric="l2",
                                   backend="pallas", chunk=64)
    wv, gv = np.asarray(wv), np.asarray(gv)
    finite = np.isfinite(wv)
    assert np.array_equal(np.isfinite(gv), finite)
    np.testing.assert_allclose(gv[finite], wv[finite], rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))


# ---------------------------------------------------------------------------
# 3. warmup + sentinel/dtype contract
# ---------------------------------------------------------------------------

def test_warmup_pretraces_the_dispatch_tables(fix):
    """After warmup(ks, buckets), a real batch that lands in a warmed
    (k, bucket) must add no new segmented-program traces."""
    eng = LabelHybridEngine.build(fix["x"], fix["ls"], mode="eis", c=0.2,
                                  backend="flat")
    k = 6
    bucket = pow2_bucket(len(fix["qls"]))
    before = ops._segmented_topk._cache_size()
    rep = eng.warmup([k], [bucket])
    assert rep["programs"] > 0 and rep["seconds"] > 0
    mid = ops._segmented_topk._cache_size()
    assert mid >= before    # first engine of this shape traces something
    d, i = eng.search_batched(fix["qv"], fix["qls"], k,
                              min_bucket=bucket)
    assert ops._segmented_topk._cache_size() == mid   # all hits
    # warmed engine answers exactly like the reference loop
    dl, il = eng.search_looped(fix["qv"], fix["qls"], k)
    np.testing.assert_array_equal(i, il)
    np.testing.assert_array_equal(d, dl)


def test_warmup_on_private_storage_backend():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((400, 16)).astype(np.float32)
    ls = generate_label_sets(400, LabelWorkloadConfig(num_labels=6, seed=2))
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="ivf",
                                  nprobe=2)
    rep = eng.warmup([4], [8])
    assert rep["programs"] == len(eng.indexes)
    qv = rng.standard_normal((10, 16)).astype(np.float32)
    qls = generate_query_label_sets(ls, 10, seed=4)
    d, i = eng.search_batched(qv, qls, 4, min_bucket=8)
    dl, il = eng.search_looped(qv, qls, 4)
    np.testing.assert_array_equal(i, il)
    np.testing.assert_array_equal(d, dl)


def test_global_id_contract_is_centralized():
    """The executor's sentinel is n itself, so n must fit int32 — the old
    bare ``astype(np.int32)`` downcast overflowed silently instead."""
    check_global_id_contract(0)
    check_global_id_contract(2**31 - 2)
    with pytest.raises(OverflowError):
        check_global_id_contract(2**31 - 1)     # sentinel == n must fit too
    with pytest.raises(OverflowError):
        check_global_id_contract(2**40)
    rows = as_row_ids(np.arange(10, dtype=np.int64), 10)
    assert rows.dtype == ROW_ID_DTYPE
    with pytest.raises(ValueError):
        as_row_ids(np.array([0, 12], dtype=np.int64), 10)   # out of range


def test_engine_rows_follow_the_contract(fix):
    eng = fix["eng"]
    assert eng.rows_concat.dtype == ROW_ID_DTYPE
    assert all(r.dtype == ROW_ID_DTYPE for r in eng.rows.values())
    d, i = eng.search_batched(fix["qv"][:4], fix["qls"][:4], 3)
    assert i.dtype == np.int32
