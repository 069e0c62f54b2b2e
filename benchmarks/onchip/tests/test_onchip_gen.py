"""The benchmark's generators against the program's own."""
import numpy as np
import onchip_testkit  # noqa: F401  (puts the benchmark on the path)

import gen
from repro.core.labels import (LabelWorkloadConfig, generate_label_sets,
                               generate_query_label_sets)

N, L = 50_000, 12


def test_vectorised_labels_match_the_program_generator():
    ours = gen.label_members(N, L, 1.5, 3.0, 8, seed=5)
    theirs = gen.as_member(generate_label_sets(
        N, LabelWorkloadConfig(num_labels=L, zipf_a=1.5, mean_set_size=3.0,
                               max_set_size=8, seed=6)), L)
    # per-label marginals: two independent binomial samples of N rows
    p1, p2 = ours.mean(axis=0), theirs.mean(axis=0)
    sigma = np.sqrt((p1 * (1 - p1) + p2 * (1 - p2)) / N)
    assert np.all(np.abs(p1 - p2) <= 5 * sigma + 1e-12)
    # set-size distribution
    s1 = np.bincount(ours.sum(axis=1), minlength=9) / N
    s2 = np.bincount(theirs.sum(axis=1), minlength=9) / N
    sigma = np.sqrt((s1 * (1 - s1) + s2 * (1 - s2)) / N)
    assert np.all(np.abs(s1 - s2) <= 5 * sigma + 1e-12)
    # and the distribution is the skewed one, not a uniform draw
    assert p1[0] > 3 * p1[-1]


def test_query_generator_is_the_papers():
    base = generate_label_sets(2000, LabelWorkloadConfig(seed=1))
    assert gen.query_label_sets(base, 300, 17) == \
        generate_query_label_sets(base, 300, seed=17)


CFG = {"n_rows": 3000, "n_labels": L, "zipf_a": 1.5,
       "mean_labels_per_row": 3.0, "max_labels_per_row": 8,
       "label_seed": 4, "dim": 8}


def test_seed_permutes_one_fixed_multiset():
    a, b = gen.Dataset(CFG, 2**31 + 11), gen.Dataset(CFG, 12)
    again = gen.Dataset(CFG, 2**31 + 11)
    assert sorted(a.sets) == sorted(b.sets) and a.sets != b.sets
    assert a.sets == again.sets
    assert np.array_equal(a.vectors, again.vectors)
    assert not np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(gen.as_member(a.sets, L), a.member)


def test_each_call_draws_fresh_queries():
    ds = gen.Dataset(CFG, 2**31 + 11)
    calls = [ds.queries(64, i) for i in range(4)]
    again = gen.Dataset(CFG, 2**31 + 11).queries(64, 2)
    assert calls[2][1] == again[1]
    assert np.array_equal(calls[2][0], again[0])
    assert len({tuple(qls) for _, qls in calls}) == 4
    for qv, qls in calls:
        assert qv.shape == (64, 8) and len(qls) == 64
        # each is a non-empty subset of some row's label set
        for q in qls:
            assert q and any(set(q) <= set(s) for s in ds.nonempty[:3000])


def test_query_labels_follow_the_seed():
    a = gen.Dataset(CFG, 5).queries(128, 0)[1]
    b = gen.Dataset(CFG, 6).queries(128, 0)[1]
    assert a != b
    # the same generator's law: label 0, the most popular, leads both
    for qls in (a, b):
        counts = np.bincount([j for q in qls for j in q], minlength=L)
        assert counts[0] == counts.max()
