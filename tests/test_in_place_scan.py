"""In-place scan of identity segments (DESIGN.md §3.10).

A segment whose row list is ``arange(N)`` — the root index, and the
streaming delta over ``[0, count)`` — is read by the unfused XLA scan as
contiguous arena chunks shared by the batch instead of per-(query, row)
gathers through the row table.  Pinned here:

  1. **kernel parity** — ``segmented_topk(in_place=True)`` equals the
     row-table program over the same identity segment bit for bit in
     ``vals``, ``pos`` and ``gid``, and the unchunked oracle
     ``ref.segmented_filtered_topk`` in ``pos``/``gid`` (and in ``vals``
     wherever the arithmetic is exact: integer data, no int8 dequant —
     the parity tiers of tests/test_fused_scan.py), across arena sizes
     the chunk does not divide (the clamped tail chunk), padded lanes
     (``lens = 0``), short cursors (``lens < N``), tombstones, both
     metrics and every storage spec the branch slices;
  2. **engine** — mixed root / non-root batches match the looped
     executor, the streaming engine matches a rebuilt engine that reads
     through the row table, warm-up covers the in-place programs, and a
     segment is marked in place exactly when its rows are ``arange(n)``;
  3. **telemetry** — ``eli_scan_in_place_queries_total`` and
     ``QueryCard.scan`` count the root's real queries.
"""
from __future__ import annotations

import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EMPTY_KEY, LabelHybridEngine, LabelWorkloadConfig,
                        StreamingEngine, brute_force_filtered,
                        generate_label_sets, generate_query_label_sets)
from repro.index.base import pow2_bucket, quantize_int8
from repro.kernels import ops, ref
from repro.obs import metrics, trace

# ---------------------------------------------------------------------------
# 1. kernel parity
# ---------------------------------------------------------------------------

SPECS = ("f32", "fp16", "int8", "fp16+rerank", "int8+rerank")
# (arena rows N, span lmax, chunk, tombstones): 300 rows leave a 44-row
# tail the last chunk's clamped slice overlaps; 40 rows are fewer than
# one chunk; 256 rows divide into whole chunks
GEOMETRIES = {"tail": (300, 512, 64, True), "short": (40, 64, 64, False),
              "whole": (256, 256, 32, True)}


def identity_case(spec: str, metric: str, N: int, lmax: int, tomb: bool,
                  *, D=24, Q=8, W=2, seed=0):
    """Operands of a batch whose every segment is the identity over an
    N-row arena, lanes covering empty, one-row, partial and full spans."""
    rng = np.random.default_rng(seed)
    xf = rng.integers(-4, 5, (N, D)).astype(np.float32)
    q = rng.integers(-4, 5, (Q, D)).astype(np.float32)
    alw = rng.integers(0, 2, (N, W)).astype(np.int32)
    lq = np.zeros((Q, W), np.int32)
    lq[:, 0] = 1
    lens = np.array([N, 0, N // 2, 1, N, N - 3, 0, N], np.int32)[:Q]
    dtype = spec.split("+")[0]
    kw = dict(metric=metric, lmax=lmax, dtype=dtype)
    if dtype == "f32":
        ax, xd = xf, xf
    elif dtype == "fp16":
        ax = xf.astype(np.float16)
        xd = ax.astype(np.float32)
    else:
        ax, scale, zero = quantize_int8(xf)
        xd = zero[:, None] + scale[:, None] * ax.astype(np.float32)
        kw.update(scales=jnp.asarray(scale), zeros=jnp.asarray(zero))
    axn = np.sum(xd * xd, axis=1).astype(np.float32)
    if spec.endswith("+rerank"):
        kw.update(rerank=jnp.asarray(xf), kprime=8,
                  rerank_norms=jnp.asarray(
                      np.sum(xf * xf, axis=1).astype(np.float32)))
    tb = (jnp.asarray(rng.integers(0, 256, ((N + 7) // 8,)).astype(np.uint8))
          if tomb else None)
    args = (jnp.asarray(q), jnp.asarray(lq), jnp.asarray(ax),
            jnp.asarray(alw), jnp.asarray(axn),
            jnp.arange(N, dtype=jnp.int32), jnp.zeros(Q, jnp.int32),
            jnp.asarray(lens))
    return args, tb, kw


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("metric", ("l2", "ip"))
@pytest.mark.parametrize("spec", SPECS)
def test_in_place_matches_row_table_and_oracle(spec, metric, geometry):
    N, lmax, chunk, tomb = GEOMETRIES[geometry]
    k = 5
    args, tb, kw = identity_case(spec, metric, N, lmax, tomb)
    tag = f"{spec} {metric} {geometry}"
    tv, tp, tg = ops.segmented_topk(*args, k=k, chunk=chunk, tomb=tb, **kw)
    iv, ip_, ig = ops.segmented_topk(*args, k=k, chunk=chunk, tomb=tb,
                                     in_place=True, **kw)
    for name, want, got in (("vals", tv, iv), ("pos", tp, ip_),
                            ("gid", tg, ig)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{tag} row table {name}")
    ov, op = ref.segmented_filtered_topk(*args, k=k, tomb=tb, **kw)
    np.testing.assert_array_equal(np.asarray(ip_), np.asarray(op),
                                  err_msg=f"{tag} oracle pos")
    # identity segment: the global id IS the position (sentinel N)
    np.testing.assert_array_equal(
        np.asarray(ig), np.where(np.asarray(op) >= lmax, N, np.asarray(op)),
        err_msg=f"{tag} oracle gid")
    if kw["dtype"] != "int8":
        np.testing.assert_array_equal(np.asarray(iv), np.asarray(ov),
                                      err_msg=f"{tag} oracle vals")
    else:
        assert np.allclose(np.asarray(iv), np.asarray(ov)), tag
    # padded lanes come back empty
    lens = np.asarray(args[7])
    assert np.all(np.asarray(ig)[lens == 0] == N)
    assert np.all(np.isinf(np.asarray(iv)[lens == 0]))


def test_in_place_tail_counts_each_row_once():
    """k = N over an arena the chunk does not divide: every row is
    returned exactly once — the clamped tail slice re-reads rows of the
    previous chunk and must mask them out, not merge them twice."""
    N, lmax, chunk = 100, 128, 32
    args, _, kw = identity_case("f32", "l2", N, lmax, False, Q=1)
    q, _, ax, alw, axn, rows, starts, _ = args
    lq = jnp.zeros((1, 2), jnp.int32)          # no label filter
    lens = jnp.full((1,), N, jnp.int32)
    vals, pos, gid = ops.segmented_topk(q, lq, ax, alw, axn, rows, starts,
                                        lens, k=N, chunk=chunk,
                                        in_place=True, **kw)
    assert sorted(np.asarray(gid)[0].tolist()) == list(range(N))
    assert np.all(np.isfinite(np.asarray(vals)))


def test_in_place_refuses_pallas_and_fused():
    args, _, kw = identity_case("f32", "l2", 64, 64, False)
    assert ops.in_place_capable("ref", False)
    assert not ops.in_place_capable("pallas", False)
    assert not ops.in_place_capable("ref", True)
    with pytest.raises(ValueError, match="in-place"):
        ops.segmented_topk(*args, k=4, chunk=32, fused=True, in_place=True,
                           **kw)


def test_in_place_program_reads_no_row_table():
    """The compiled in-place program slices the arena: the only gather
    left is the merge's [Q, k] take_along_axis, and the distance is the
    multiply + minor-axis reduce (no dot, no convolution)."""
    N, D, W, Q, lmax = 1000, 16, 2, 8, 1024
    f32, i32 = jnp.float32, jnp.int32
    text = ops._segmented_topk.lower(
        jnp.zeros((Q, D), f32), jnp.zeros((Q, W), i32),
        jnp.zeros((N, D), f32), jnp.zeros((N, W), i32), jnp.zeros((N,), f32),
        None, jnp.zeros((Q,), i32), jnp.full((Q,), N, i32),
        k=4, lmax=lmax, chunk=256, metric="l2", backend="ref",
        interpret=True, in_place=True).compile().as_text()
    assert not re.search(r"\b(dot|convolution)\(", text)
    gathers = re.findall(r'gather\(.*op_name="([^"]*)"', text)
    assert all("scan.merge" in name for name in gathers), gathers
    assert "scan.rows/dynamic_slice" in text


# ---------------------------------------------------------------------------
# 2. engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(5)
    N, D, Q = 3000, 16, 160
    x = rng.standard_normal((N, D)).astype(np.float32)
    ls = generate_label_sets(N, LabelWorkloadConfig(num_labels=8, seed=3))
    qv = rng.standard_normal((Q, D)).astype(np.float32)
    # a fifth of the batch has no label: it routes to the root
    qls = generate_query_label_sets(ls, Q - Q // 5, seed=4,
                                    from_base_fraction=0.75)
    qls += [()] * (Q // 5)
    order = rng.permutation(Q)
    qls = [qls[i] for i in order]
    pool_x = rng.standard_normal((400, D)).astype(np.float32)
    pool_ls = generate_label_sets(400, LabelWorkloadConfig(num_labels=8,
                                                           seed=21))
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    return dict(x=x, ls=ls, qv=qv, qls=qls, N=N, eng=eng, pool_x=pool_x,
                pool_ls=pool_ls)


def _root_queries(eng, qls) -> int:
    return sum(key == EMPTY_KEY for key in eng.route_many(qls))


def test_mixed_root_batch_matches_looped(data):
    eng, qv, qls = data["eng"], data["qv"], data["qls"]
    routed = eng.route_many(qls)
    assert EMPTY_KEY in routed and any(key != EMPTY_KEY for key in routed)
    assert eng.in_place_keys == {EMPTY_KEY}
    for k in (1, 10):
        d_b, i_b = eng.search_batched(qv, qls, k)
        d_l, i_l = eng.search_looped(qv, qls, k)
        np.testing.assert_array_equal(i_b, i_l, err_msg=f"k={k}")
        np.testing.assert_array_equal(d_b, d_l, err_msg=f"k={k}")


def test_in_place_marking_follows_the_row_list(data):
    """Marked in place exactly when the rows are arange(n): the root
    as built; not a reversed root; any key handed arange(n) (a superset
    of its closure still answers exactly: the label filter runs per
    row).  Engines whose scan has no in-place path mark nothing."""
    x, ls, qv, qls, n = (data[key] for key in ("x", "ls", "qv", "qls", "N"))
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    ident = np.arange(n)
    for key, rows in eng.rows.items():
        assert (key in eng.in_place_keys) == np.array_equal(rows, ident)
    other = next(key for key in eng.rows if key != EMPTY_KEY)
    eng.rows[EMPTY_KEY] = eng.rows[EMPTY_KEY][::-1].copy()
    eng.rows[other] = ident.astype(np.int32)
    eng.apply_selection(eng.selection)
    assert eng.in_place_keys == {other}
    d_t, i_t = brute_force_filtered(x, ls, qv, qls, 10)
    d_b, i_b = eng.search_batched(qv, qls, 10)
    # the reversed root lists rows in descending order, so equal
    # distances may come back in another order: compare the distances
    np.testing.assert_allclose(d_b, d_t, rtol=1e-5, atol=1e-4)
    for kw in ({"fused": True}, {"kernel_backend": "pallas"}):
        assert not LabelHybridEngine.build(x, ls, mode="eis", c=0.2,
                                           backend="flat",
                                           **kw).in_place_keys
    assert not LabelHybridEngine.build(x, ls, mode="eis", c=0.2,
                                       backend="ivf",
                                       nprobe=4).in_place_keys


def test_warmup_covers_the_in_place_programs(data):
    eng, qv, qls = data["eng"], data["qv"], data["qls"]
    k, bucket = 7, pow2_bucket(len(qls))
    tiers = eng.span_tiers()
    assert (pow2_bucket(data["N"]), True) in tiers
    rep = eng.warmup([k], [bucket])
    assert rep["programs"] == len(tiers)
    before = ops._segmented_topk._cache_size()
    eng.search_batched(qv, qls, k, min_bucket=bucket)
    assert ops._segmented_topk._cache_size() == before


def test_streaming_parity_with_row_table_rebuild(data):
    """Deletes, inserts and a compaction: the streaming engine (root and
    delta read in place) equals an engine rebuilt from the survivors that
    reads every segment through the row table."""
    se = StreamingEngine.build(data["x"], data["ls"], mode="eis", c=0.2,
                               backend="flat", max_delta_fraction=None,
                               max_tombstone_fraction=None)
    qv, qls = data["qv"], data["qls"]
    rng = np.random.default_rng(9)

    def check(tag):
        alive_base, alive_delta = ~se._base_dead, ~se._delta_dead
        n_base = len(se.base.label_sets)
        parts = [se.base.vectors[alive_base]]
        if se._n_inserted:
            parts.append(np.concatenate(se._delta_vec_parts)[alive_delta])
        surv_ls = ([s for s, a in zip(se.base.label_sets, alive_base) if a]
                   + [s for s, a in zip(se._delta_ls, alive_delta) if a])
        surv_ids = np.concatenate([np.flatnonzero(alive_base),
                                   n_base + np.flatnonzero(alive_delta)])
        oracle = LabelHybridEngine.build(np.concatenate(parts), surv_ls,
                                         mode="eis", c=0.2, backend="flat")
        oracle.in_place_keys = frozenset()      # row-table reads only
        assert se.base.in_place_keys == {EMPTY_KEY}
        for k in (1, 10):
            d_s, i_s = se.search_batched(qv, qls, k)
            d_o, i_o = oracle.search_batched(qv, qls, k)
            i_o = np.where(i_o < surv_ids.size,
                           surv_ids[np.clip(i_o, 0, surv_ids.size - 1)],
                           se.sentinel).astype(np.int32)
            np.testing.assert_array_equal(i_s, i_o, err_msg=f"{tag} k={k}")
            np.testing.assert_array_equal(d_s, d_o, err_msg=f"{tag} k={k}")

    ids = se.insert(data["pool_x"][:250], data["pool_ls"][:250])
    se.delete(rng.choice(data["N"], 200, replace=False))
    se.delete(ids[::7])
    check("pending")
    se.flush()
    check("compacted")
    se.insert(data["pool_x"][250:], data["pool_ls"][250:])
    se.delete(np.arange(0, 1500, 11))
    check("round two")


# ---------------------------------------------------------------------------
# 3. telemetry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_root", (True, False))
def test_in_place_counter_and_cards(data, with_root):
    eng, qv, qls = data["eng"], data["qv"], data["qls"]
    if not with_root:
        keep = [i for i, key in enumerate(eng.route_many(qls))
                if key != EMPTY_KEY]
        qv, qls = qv[keep], [qls[i] for i in keep]
    expect = _root_queries(eng, qls)
    assert (expect > 0) == with_root
    counter = metrics.REGISTRY.get("eli_scan_in_place_queries_total")
    trace.enable()
    trace.reset()
    try:
        before = counter.value()
        eng.search_batched(qv, qls, 10)
        got = counter.value() - before
        cards = list(trace.iter_cards())
    finally:
        trace.disable()
    assert got == expect
    assert sum(c.n_queries for c in cards if c.scan == "in_place") == expect
    assert sum(c.n_queries for c in cards
               if c.scan == "row_table") == len(qls) - expect
