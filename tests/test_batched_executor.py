"""Batched multi-index executor: parity against the per-key reference loop
(bit-identical) and the brute-force ground truth, vectorized routing parity
with route() — including the fallback for query keys outside the selection
workload — and the jit-cache behavior of the bucketed dispatch."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (LabelHybridEngine, LabelWorkloadConfig,
                        brute_force_filtered, encode_label_set,
                        generate_label_sets, generate_query_label_sets,
                        key_contains, mask_key, recall_at_k)

K = 10


@pytest.fixture(scope="module")
def fix():
    """10k vectors / 500 queries (the ISSUE acceptance fixture), with a
    mixed query workload: ~75% subsets of base label sets (seen keys) and
    ~25% uniform label-universe subsets (mostly unseen keys), plus a few
    hand-picked never-co-occurring combinations."""
    rng = np.random.default_rng(11)
    N, D, Q = 10_000, 32, 500
    x = rng.standard_normal((N, D)).astype(np.float32)
    ls = generate_label_sets(N, LabelWorkloadConfig(num_labels=10, seed=3))
    qv = rng.standard_normal((Q, D)).astype(np.float32)
    qls = generate_query_label_sets(ls, Q - 4, seed=4,
                                    from_base_fraction=0.75)
    # force the unseen-key fallback path: large combinations that no base
    # entry (max_set_size=8 over 10 labels) is guaranteed to have produced
    qls += [(0, 1, 2, 3, 4, 5), (2, 3, 4, 5, 6, 7, 8, 9),
            (0, 2, 4, 6, 8), ()]
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    return dict(x=x, ls=ls, qv=qv, qls=qls, eng=eng, N=N)


def test_batched_bitwise_matches_loop(fix):
    d_loop, i_loop = fix["eng"].search_looped(fix["qv"], fix["qls"], K)
    d_bat, i_bat = fix["eng"].search_batched(fix["qv"], fix["qls"], K)
    np.testing.assert_array_equal(i_bat, i_loop)
    np.testing.assert_array_equal(d_bat, d_loop)


def test_batched_matches_ground_truth(fix):
    gt_d, gt_i = brute_force_filtered(fix["x"], fix["ls"], fix["qv"],
                                      fix["qls"], K)
    _, i_bat = fix["eng"].search_batched(fix["qv"], fix["qls"], K)
    assert recall_at_k(i_bat, gt_i, fix["N"]) == pytest.approx(1.0)


def test_default_search_is_batched(fix):
    d_def, i_def = fix["eng"].search(fix["qv"][:33], fix["qls"][:33], K)
    d_bat, i_bat = fix["eng"].search_batched(fix["qv"][:33], fix["qls"][:33],
                                             K)
    np.testing.assert_array_equal(i_def, i_bat)
    np.testing.assert_array_equal(d_def, d_bat)


def test_route_many_matches_route(fix):
    eng = fix["eng"]
    vec = eng.route_many(fix["qls"])
    ref = [eng.route(tuple(q)) for q in fix["qls"]]
    assert vec == ref
    # the fixture must actually exercise the unseen-key fallback
    seen = set(eng.selection.assignment)
    assert any(mask_key(encode_label_set(q)) not in seen for q in fix["qls"])


def test_unseen_key_routes_to_containing_index(fix):
    eng = fix["eng"]
    for q in [(0, 1, 2, 3, 4, 5), (0, 2, 4, 6, 8)]:
        [key] = eng.route_many([q])
        assert key_contains(mask_key(encode_label_set(q)), key)
        assert key == eng.route(q)


def test_bucket_jit_cache_is_reused(fix):
    """The batched arena path dispatches one jit-cached segmented program
    per (k, Q-bucket, span tier): an identical batch lands in the same
    programs and compiles none."""
    from repro.kernels import ops
    eng = fix["eng"]
    eng.search_batched(fix["qv"][:100], fix["qls"][:100], K)
    size = ops._segmented_topk._cache_size()
    assert size > 0                          # the bucketed path was taken
    eng.search_batched(fix["qv"][:100], fix["qls"][:100], K)
    assert ops._segmented_topk._cache_size() == size


def test_empty_and_single_query_batches(fix):
    eng = fix["eng"]
    d0, i0 = eng.search_batched(fix["qv"][:0], [], K)
    assert d0.shape == (0, K) and i0.shape == (0, K)
    d1, i1 = eng.search_batched(fix["qv"][:1], fix["qls"][:1], K)
    dl, il = eng.search_looped(fix["qv"][:1], fix["qls"][:1], K)
    np.testing.assert_array_equal(i1, il)
    np.testing.assert_array_equal(d1, dl)


def test_bucket_caches_isolated_across_engines_and_k():
    """Regression for the bucket-cache bug class (ISSUE 2): two engines
    with different k sharing one process must not cross-contaminate
    dispatch caches — the key must pin index identity (by living on the
    instance, see index.base.bucket_cache), k, and bucket.  Under the
    arena (ISSUE 3) the batched hot path is one engine-level segmented
    program (jit-keyed on k + shapes, contamination-free by construction);
    the per-instance tables now belong to the per-view looped/direct path,
    so that is where isolation is asserted."""
    from repro.core import generate_label_sets, generate_query_label_sets

    rng = np.random.default_rng(5)
    x = rng.standard_normal((600, 16)).astype(np.float32)
    ls = generate_label_sets(600, LabelWorkloadConfig(num_labels=8, seed=9))
    qv = rng.standard_normal((40, 16)).astype(np.float32)
    qls = generate_query_label_sets(ls, 40, seed=10)
    e1 = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    e2 = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    # interleave the two engines so a shared/global cache would collide on
    # identical (bucket, shapes) with different k or different index data
    d1, i1 = e1.search_batched(qv, qls, 3)
    d2, i2 = e2.search_batched(qv, qls, 7)
    d1b, i1b = e1.search_batched(qv, qls, 3)
    np.testing.assert_array_equal(i1, i1b)
    np.testing.assert_array_equal(d1, d1b)
    # both engines agree with their reference loops (which dispatch through
    # the per-view bucket tables — populating them)
    np.testing.assert_array_equal(i1, e1.search_looped(qv, qls, 3)[1])
    np.testing.assert_array_equal(i2, e2.search_looped(qv, qls, 7)[1])
    seen = 0
    for key in e1.indexes:
        c1 = getattr(e1.indexes[key], "_bucket_fns", None)
        c2 = getattr(e2.indexes[key], "_bucket_fns", None)
        if not c1 and not c2:
            continue
        seen += 1
        assert (c1 or {}) is not (c2 or {})      # per-instance tables
        assert all(kk[0] == 3 for kk in (c1 or {})), c1  # each pins its own k
        assert all(kk[0] == 7 for kk in (c2 or {})), c2
    assert seen                                  # bucketed path was taken
