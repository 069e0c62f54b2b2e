"""The copied reference and the comparison that decides ``correct``."""
import numpy as np
import onchip_testkit  # noqa: F401

import reference


def _case(n=600, d=8, L=5, q=24, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    member = rng.random((n, L)) < 0.5
    qv = rng.standard_normal((q, d)).astype(np.float32)
    qmasks = rng.random((q, L)) < 0.3
    return x, member, qv, qmasks


def _brute(x, member, qv, qmasks, k, lo, hi):
    ids, dist = [], []
    for q, qm in zip(qv.astype(np.float64), qmasks):
        rows = [i for i in range(lo, hi) if member[i][qm].all()]
        d = [float(np.sum((x[i].astype(np.float64) - q) ** 2)) for i in rows]
        order = sorted(range(len(rows)), key=lambda j: (d[j], rows[j]))[:k]
        ids.append([rows[j] for j in order] + [-1] * (k - len(order)))
        dist.append([d[j] for j in order] + [np.inf] * (k - len(order)))
    return np.array(ids), np.array(dist)


def test_reference_equals_a_direct_brute_force():
    x, member, qv, qmasks = _case()
    qmasks[3] = True          # a filter that few or no rows pass
    ref = reference.Reference(x, member)
    for lo, hi in ((0, 600), (100, 450)):
        got_i, got_d = ref.topk(qv, qmasks, 10, lo, hi)
        want_i, want_d = _brute(x, member, qv, qmasks, 10, lo, hi)
        assert np.array_equal(got_i, want_i)
        np.testing.assert_allclose(got_d, want_d, rtol=1e-12, atol=1e-12)


def test_compare_passes_exact_answers_and_flags_each_fault():
    x, member, qv, qmasks = _case(seed=1)
    ref = reference.Reference(x, member)
    want_i, want_d = ref.topk(qv, qmasks, 10, 0, 600)
    exact = reference.compare(ref, qv, qmasks, want_i,
                              want_d.astype(np.float32), 0, 600, want_i,
                              want_d)
    assert reference.within(exact)

    def numbers(ids, d):
        return reference.compare(ref, qv, qmasks, ids, d, 0, 600, want_i,
                                 want_d)

    full = next(i for i in range(len(qv))
                if qmasks[i].any() and (want_i[i] >= 0).all())
    altered = want_i.copy()          # a passing row that is not the 1st
    passing = np.flatnonzero(ref.passes(qmasks[full], 0, 600))
    spare = [r for r in passing if r not in set(want_i[full])][0]
    altered[full, 0] = spare
    assert numbers(altered, want_d)["rank_gap"] > reference.LIMITS[
        "rank_gap"]
    half = want_i.copy()
    half[len(half) // 2:] = -1       # half of the batch left out
    assert numbers(half, want_d)["bad_queries"] > 0
    failing = want_i.copy()
    failing[full, 0] = np.flatnonzero(~ref.passes(qmasks[full], 0, 600))[0]
    assert numbers(failing, want_d)["bad_queries"] == 1
    dead = want_i.copy()             # a row outside the live range
    assert reference.compare(ref, qv, qmasks, dead, want_d, 0,
                             int(want_i.max()), want_i, want_d)[
        "bad_queries"] > 0
    low = (want_d * (1 + 1e-4)).astype(np.float32)
    assert numbers(want_i, low)["dist_err"] > reference.LIMITS["dist_err"]
