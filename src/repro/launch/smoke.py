"""End-to-end smoke of the store's main path (``chip_smoke.py``).

:func:`run_phases` builds one engine from a seed and drives it, in one
process, through the entry points a user calls:

  a. ``search_batched`` on the XLA scan (``kernel_backend="ref"``);
  b. the same queries on the fused Pallas scan, f32 and ``int8+rerank``;
  c. a ``StreamingEngine`` round: insert, delete, search, flush, search;
  d. a ``ServingRuntime`` answering requests after warmup.

:func:`run_sharded` drives the ``distributed`` backend over a 1-D mesh of
every local device against the one-device flat engine.

Correctness is decided by :class:`HostReference`, a NumPy float64 brute
force over the surviving rows that shares no code with the engine.  A
failed check raises :class:`SmokeFailure` at once; no phase is skipped or
caught so that a later one can run.  Each phase reports its lines through
``log``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import numpy as np


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


K = 10                  # neighbours per query
C = 0.2                 # EIS elastic-factor bound (the paper's ELI-0.2)
MIN_INT8_RECALL = 0.99  # gate for the int8+rerank tier


@dataclasses.dataclass(frozen=True)
class SmokeSizes:
    """The workload: the paper's base size (``configs/eli_paper.py``) with
    its label generator, 12 labels."""
    n: int = 1_000_000
    dim: int = 128
    n_labels: int = 12
    n_queries: int = 256
    stream_rows: int = 10_000
    n_requests: int = 32


def make_data(sizes: SmokeSizes, seed: int):
    """(vectors, label sets, query vectors, query label sets) from ``seed``:
    Gaussian rows, Zipf(1.5) labels with a mean of 3 per row (UNG's
    generator, ``data.pipeline.VectorLabelDataset``), query label sets drawn
    as subsets of base label sets so every query has a filtered set."""
    from ..core.labels import generate_query_label_sets
    from ..data.pipeline import VectorLabelDataset
    x, ls = VectorLabelDataset(n=sizes.n, dim=sizes.dim,
                               n_labels=sizes.n_labels, seed=seed).generate()
    rng = np.random.default_rng(seed + 1)
    qv = rng.standard_normal((sizes.n_queries, sizes.dim)).astype(np.float32)
    qls = generate_query_label_sets(ls, sizes.n_queries, seed=seed + 2)
    return x, ls, qv, qls


class HostReference:
    """Exact filtered k-NN in float64 on the host over ``x`` rows whose
    ``alive`` bit is set: squared L2, a row passes a query iff it carries
    every query label.  Ids are row numbers of ``x``."""

    def __init__(self, x: np.ndarray, label_sets: Sequence[tuple[int, ...]],
                 n_labels: int, alive: np.ndarray | None = None):
        self.x = np.asarray(x, np.float64)
        self.xn = np.einsum("ij,ij->i", self.x, self.x)
        self.xn_median = float(np.median(self.xn))
        self.has = np.zeros((len(label_sets), n_labels), bool)
        for i, labels in enumerate(label_sets):
            self.has[i, list(labels)] = True
        self.alive = (np.ones(len(label_sets), bool) if alive is None
                      else np.asarray(alive, bool))

    def passes(self, labels: tuple[int, ...]) -> np.ndarray:
        return self.alive & np.all(self.has[:, list(labels)], axis=1)

    def dist(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        q = np.asarray(q, np.float64)
        return self.xn[ids] - 2.0 * (self.x[ids] @ q) + q @ q

    def topk(self, qv: np.ndarray, qls, k: int, block: int = 32):
        """(ids [Q, k] with -1 where fewer than k rows pass, f64 distances
        [Q, k] with +inf there), ascending."""
        q64 = np.asarray(qv, np.float64)
        ids = np.full((len(qls), k), -1, np.int64)
        dist = np.full((len(qls), k), np.inf)
        for lo in range(0, len(qls), block):
            qb = q64[lo:lo + block]
            d = (self.xn[:, None] - 2.0 * (self.x @ qb.T)
                 + np.einsum("ij,ij->i", qb, qb)[None, :])
            for j in range(qb.shape[0]):
                dj = np.where(self.passes(qls[lo + j]), d[:, j], np.inf)
                m = min(k, int(np.isfinite(dj).sum()))
                if m == 0:
                    continue
                top = np.argpartition(dj, m - 1)[:m]
                top = top[np.argsort(dj[top], kind="stable")]
                ids[lo + j, :m] = top
                dist[lo + j, :m] = dj[top]
        return ids, dist


def exact_mismatches(ref: HostReference, qv, qls, got_ids: np.ndarray,
                     sentinel: int, want_ids: np.ndarray,
                     want_d: np.ndarray, rtol: float = 1e-5):
    """Queries whose result is not an exact filtered top-k, up to distance
    ties within f32 rounding: the same number of results as the reference,
    every id a live row that passes the filter, and the float64 distances
    of the returned rows, sorted, equal to the reference's to within
    ``rtol`` of the query's distance scale.  Returns (bad query indices,
    largest distance gap seen)."""
    bad, worst = [], 0.0
    for i, labels in enumerate(qls):
        m = int((want_ids[i] >= 0).sum())
        got = got_ids[i][got_ids[i] < sentinel].astype(np.int64)
        if got.size != m or (m and not ref.passes(labels)[got].all()):
            bad.append(i)
            continue
        if m == 0:
            continue
        d = np.sort(ref.dist(qv[i], got))
        gap = float(np.max(np.abs(d - want_d[i, :m])))
        worst = max(worst, gap)
        scale = float(qv[i].astype(np.float64) @ qv[i]) + ref.xn_median
        if gap > rtol * scale:
            bad.append(i)
    return bad, worst


def check_exact(tag, ref: HostReference, qv, qls, got_ids, sentinel,
                want_ids, want_d, log) -> None:
    """Log and require :func:`exact_mismatches` to find nothing."""
    bad, worst = exact_mismatches(ref, qv, qls, got_ids, sentinel, want_ids,
                                  want_d)
    log(f"[{tag}] exact vs host float64 on {len(qls) - len(bad)}/"
        f"{len(qls)} queries (largest distance gap {worst:.3g})")
    check(not bad, f"{tag}: {len(bad)} queries differ from the reference, "
                   f"first {bad[:5]}")


def recall(got_ids: np.ndarray, want_ids: np.ndarray) -> float:
    hit = total = 0
    for g, w in zip(got_ids, want_ids):
        w = set(int(v) for v in w if v >= 0)
        total += len(w)
        hit += len(w & set(int(v) for v in g))
    return hit / max(total, 1)


def _executor(eng) -> str:
    """Which scan the engine's searches run: XLA's gather, or the Pallas
    kernel compiled for the chip or interpreted."""
    from ..kernels import ops
    if eng.backend != "flat":
        return f"{eng.backend} backend (XLA)"
    if eng._seg_backend != "pallas":
        return "XLA segmented scan (kernel_backend=ref)"
    mode = "interpreted" if ops.default_interpret() else "compiled"
    return (f"Pallas {'fused' if eng._seg_fused else 'unfused'} scan "
            f"({mode}), storage={eng.storage}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run_phases(sizes: SmokeSizes, seed: int,
               log: Callable[[str], None] = print) -> dict:
    """Phases a-d (module docstring) at ``sizes``; raises SmokeFailure on
    the first failed check.  Returns the per-phase summary."""
    import jax

    from .. import arch as A
    from ..configs import reduced_arch
    from ..core.engine import LabelHybridEngine
    from ..core.labels import LabelWorkloadConfig, generate_label_sets
    from ..core.stream import StreamingEngine
    from ..models.common import init_params
    from ..serve import (BatchedDecoder, Request, RetrievalAugmentedEngine,
                         ServeStatus, ServingRuntime)

    k, out = K, {}
    (x, ls, qv, qls), dt = _timed(lambda: make_data(sizes, seed))
    log(f"[data] {x.shape[0]:,} x {x.shape[1]} f32 rows, "
        f"{sizes.n_labels} labels, {len(qls)} filtered queries, "
        f"k={k}: {dt:.1f}s")
    ref, dt = _timed(lambda: HostReference(x, ls, sizes.n_labels))
    (want_i, want_d), dt2 = _timed(lambda: ref.topk(qv, qls, k))
    log(f"[reference] host float64 brute force: {dt + dt2:.1f}s")

    def exact(tag, eng_ids, sentinel, r=ref, wi=want_i, wd=want_d):
        check_exact(tag, r, qv, qls, eng_ids, sentinel, wi, wd, log)

    # -- a: XLA segmented scan --
    eng, dt = _timed(lambda: LabelHybridEngine.build(
        x, ls, mode="eis", c=C, backend="flat"))
    st = eng.stats()
    log(f"[a] build: {dt:.1f}s, {st.n_selected} indexes, achieved "
        f"c={st.achieved_c:.3f}, arena tiers {eng.arena.tier_nbytes}")
    (d_a, i_a), t_first = _timed(lambda: eng.search_batched(qv, qls, k))
    (d_a, i_a), t_warm = _timed(lambda: eng.search_batched(qv, qls, k))
    log(f"[a] executor: {_executor(eng)}; search {len(qls)} queries: "
        f"first {t_first:.2f}s (compile incl.), warm {t_warm:.3f}s")
    exact("a", i_a, eng.sentinel)
    out["a"] = {"first_s": t_first, "warm_s": t_warm}

    # -- b: fused Pallas scan; these engines reuse phase a's selection
    # (the constructor LabelHybridEngine.build calls after selecting) --
    for storage in ("f32", "int8+rerank"):
        eb, dt = _timed(lambda: LabelHybridEngine(
            x, ls, eng.table, eng.selection, None, "flat", "l2",
            {"kernel_backend": "pallas", "fused": True}, 0.0,
            storage=storage))
        (d_b, i_b), t_first = _timed(lambda: eb.search_batched(qv, qls, k))
        (d_b, i_b), t_warm = _timed(lambda: eb.search_batched(qv, qls, k))
        log(f"[b {storage}] executor: {_executor(eb)}; arena tiers "
            f"{eb.arena.tier_nbytes}; materialize {dt:.1f}s; search: "
            f"first {t_first:.2f}s (compile incl.), warm {t_warm:.3f}s")
        same = int((i_b == i_a).sum())
        log(f"[b {storage}] ids equal to the XLA path: {same}/{i_a.size}")
        if storage == "f32":
            exact(f"b {storage}", i_b, eb.sentinel)
        else:
            rec = recall(i_b, want_i)
            log(f"[b {storage}] recall@{k} vs host float64: {rec:.4f}")
            check(rec >= MIN_INT8_RECALL,
                  f"int8+rerank recall@{k} {rec:.4f} < {MIN_INT8_RECALL}")
        out[f"b_{storage}"] = {"first_s": t_first, "warm_s": t_warm,
                               "ids_equal_xla": same}
        del eb

    # -- c: streaming round over the phase-a engine --
    m = sizes.stream_rows
    rng = np.random.default_rng(seed + 3)
    new_x = rng.standard_normal((m, sizes.dim)).astype(np.float32)
    new_ls = generate_label_sets(m, LabelWorkloadConfig(
        num_labels=sizes.n_labels, seed=seed + 4))
    dead = rng.choice(sizes.n, size=m, replace=False)
    se = StreamingEngine(eng)
    t0 = time.perf_counter()
    ins = se.insert(new_x, new_ls)
    check(np.array_equal(ins, np.arange(sizes.n, sizes.n + m)),
          "insert returned unexpected stream ids")
    se.delete(dead)
    t_mut = time.perf_counter() - t0
    alive = np.ones(sizes.n + m, bool)
    alive[dead] = False
    sref = HostReference(np.concatenate([x, new_x]), list(ls) + new_ls,
                         sizes.n_labels, alive=alive)
    s_i, s_d = sref.topk(qv, qls, k)
    (d_c, i_c), t_s1 = _timed(lambda: se.search_batched(qv, qls, k))
    log(f"[c] executor: {_executor(se.base)} + delta scan; insert "
        f"{m:,} + delete {m:,}: {t_mut:.2f}s; search with delta and "
        f"tombstones: {t_s1:.2f}s")
    exact("c pending", i_c, se.sentinel, sref, s_i, s_d)
    rep, t_flush = _timed(se.flush)
    old_of_new = np.flatnonzero(rep["id_map"] >= 0)
    check(old_of_new.size == sizes.n, "flush kept the wrong row count")
    (d_c2, i_c2), t_s2 = _timed(lambda: se.search_batched(qv, qls, k))
    i_c2 = np.where(i_c2 < old_of_new.size,
                    old_of_new[np.clip(i_c2, 0, old_of_new.size - 1)],
                    sref.x.shape[0])
    log(f"[c] flush (compaction): {t_flush:.2f}s; search after flush: "
        f"{t_s2:.2f}s")
    exact("c flushed", i_c2, sref.x.shape[0], sref, s_i, s_d)
    out["c"] = {"mutate_s": t_mut, "flush_s": t_flush}
    del sref, ref

    # -- d: serving runtime (the decoder of examples/rag_serve.py) --
    spec = reduced_arch("mamba2_130m")
    params = init_params(jax.random.PRNGKey(seed), A.param_specs(spec))
    dec = BatchedDecoder(spec, params, batch_slots=4, max_len=96)
    rag = RetrievalAugmentedEngine(dec, se.base, k=4)
    rt, t_warm = _timed(lambda: ServingRuntime(rag))
    prng = np.random.default_rng(seed + 5)
    results = []
    t0 = time.perf_counter()
    for i in range(sizes.n_requests):
        prompt = prng.integers(0, spec.cfg.vocab,
                               size=int(prng.integers(4, 12)))
        results.append(rt.submit(Request(
            prompt=prompt.astype(np.int32), max_new=10,
            label_set=tuple(qls[i % len(qls)]), rid=i)))
    rt.run_until_idle(max_seconds=900.0)
    t_serve = time.perf_counter() - t0
    statuses = [r.status for r in results]
    n_ok = sum(s is ServeStatus.OK for s in statuses)
    log(f"[d] executor: {_executor(se.base)} + {spec.arch_id} decoder "
        f"(reduced widths); warmup {t_warm:.1f}s; {sizes.n_requests} "
        f"requests in {t_serve:.2f}s: {n_ok} OK, statuses "
        f"{sorted({s.name for s in statuses})}")
    not_ok = [(r.request.rid, r.status.name, r.error) for r in results
              if r.status is not ServeStatus.OK]
    check(not not_ok, f"{len(not_ok)} requests not OK: {not_ok[:5]}")
    sentinel = se.base.sentinel
    for r in results:
        nb = np.asarray(r.request.neighbors)
        nb = nb[nb < sentinel]
        check(nb.size > 0 and all(
            set(r.request.label_set) <= set(se.base.label_sets[int(j)])
            for j in nb), f"request {r.request.rid}: neighbors fail filter")
    rt.assert_no_new_traces()
    log(f"[d] new segmented traces after warmup: "
        f"{rt.stats().new_segmented_traces}")
    out["d"] = {"warmup_s": t_warm, "serve_s": t_serve}
    return out


def run_sharded(sizes: SmokeSizes, seed: int,
                log: Callable[[str], None] = print) -> dict:
    """The ``distributed`` backend over a 1-D ``data`` mesh of every local
    device, against the host reference and the flat engine's result (its
    arrays on the default device).  Checks that each index's row shards sit
    on distinct devices.  Raises SmokeFailure on the first failed check."""
    import jax

    from ..core.engine import LabelHybridEngine

    k = K
    n_dev = len(jax.devices())
    (x, ls, qv, qls), dt = _timed(lambda: make_data(sizes, seed))
    log(f"[data] {x.shape[0]:,} x {x.shape[1]} f32 rows, "
        f"{sizes.n_labels} labels, {len(qls)} filtered queries, k={k}, "
        f"{n_dev} devices: {dt:.1f}s")
    ref = HostReference(x, ls, sizes.n_labels)
    want_i, want_d = ref.topk(qv, qls, k)

    def exact(tag, eng_ids, sentinel):
        check_exact(tag, ref, qv, qls, eng_ids, sentinel, want_i, want_d,
                    log)

    flat, dt = _timed(lambda: LabelHybridEngine.build(
        x, ls, mode="eis", c=C, backend="flat"))
    (_, i_flat), t_flat = _timed(lambda: flat.search_batched(qv, qls, k))
    log(f"[flat] build {dt:.1f}s; executor: {_executor(flat)} on "
        f"{flat.arena.vectors.devices()}; search: {t_flat:.2f}s")
    exact("flat", i_flat, flat.sentinel)

    dist, dt = _timed(lambda: LabelHybridEngine(
        x, ls, flat.table, flat.selection, None, "distributed", "l2", {},
        0.0))
    for key, ix in dist.indexes.items():
        devs = [s.device for s in ix.x.addressable_shards]
        check(len(set(devs)) == n_dev == len(devs),
              f"index {key}: row shards on {devs}")
        rows = {s.data.shape[0] for s in ix.x.addressable_shards}
        check(len(rows) == 1, f"index {key}: uneven shards {rows}")
    log(f"[sharded] {len(dist.indexes)} indexes, each over {n_dev} "
        f"distinct devices {sorted(str(d) for d in jax.devices())}; "
        f"materialize {dt:.1f}s")
    (_, i_d), t_first = _timed(lambda: dist.search_batched(qv, qls, k))
    (_, i_d), t_warm = _timed(lambda: dist.search_batched(qv, qls, k))
    log(f"[sharded] executor: {_executor(dist)}; search: first "
        f"{t_first:.2f}s (compile incl.), warm {t_warm:.3f}s")
    exact("sharded", i_d, dist.sentinel)
    # both match the reference up to ties, hence each other
    same = int((i_d == i_flat).sum())
    log(f"[sharded] ids equal to the one-device flat result: "
        f"{same}/{i_flat.size}")
    return {"first_s": t_first, "warm_s": t_warm, "ids_equal_flat": same}
