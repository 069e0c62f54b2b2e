"""Exp-9 (Table 1 "Index Flexibility" claim): the SAME ELI selection runs
over all four registered index backends — flat (arena-backed segmented
scan), IVF (nprobe clusters), graph (Vamana beam search), distributed
(shard_map scan + top-k merge) — recall/QPS per backend at fixed c=0.2.
The selection algorithm, routing, and sub-index membership are identical;
only the physical index changes (paper §1: "not constrained by index
type").

Three measurements land in ``BENCH_exp9.json``:

  * the executor grid: every backend through BOTH executors — the
    single-dispatch segmented/bucketed ``search_batched`` hot path and the
    per-key ``search_looped`` reference — cold (first call, tracing +
    compilation included) and warm (steady state);
  * ``warmup``: cold-start shrinkage from ``engine.warmup(ks, buckets)``,
    measured per backend with every compile cache cleared first
    (``common.cold_compiles``) — targets the 11.8 s distributed cold
    batched path recorded pre-arena;
  * ``flat_sweep``: warm QPS of both executors as the selection size grows
    (c sweep) — the arena executor's launches scale with span tiers, not
    with ``n_indexes``, so its warm QPS must stay flat while the per-key
    loop degrades.
"""
import tempfile
import time

from repro.core import LabelHybridEngine
from repro.index.base import pow2_bucket

from .common import (cold_compiles, emit, emit_json, ground_truth,
                     make_dataset, measure_modes)

BACKENDS = (
    ("flat", {}),
    ("ivf", {"n_clusters": 32, "nprobe": 8}),
    ("graph", {"M": 12, "ef_search": 64}),
    ("distributed", {}),
)

def workload_buckets(eng, qls) -> list[int]:
    """The Q-buckets a query workload will induce: per span tier on the
    arena path, per routed group on the private-storage path.  A server
    derives these from its batch-size distribution the same way."""
    routed = eng.route_many(qls)
    counts: dict = {}
    if eng.arena is not None:
        for key in routed:
            lb = pow2_bucket(eng.segments[key][1])
            counts[lb] = counts.get(lb, 0) + 1
    else:
        for key in routed:
            counts[key] = counts.get(key, 0) + 1
    return sorted({pow2_bucket(c) for c in counts.values()})


def _measure_warmup(backend: str, params: dict, n: int, k: int) -> dict:
    with cold_compiles():
        x, ls, qv, qls = make_dataset(n=n, n_labels=12, q=80, seed=7)
        eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2,
                                      backend=backend, **params)
        rep = eng.warmup([k], workload_buckets(eng, qls))
        t0 = time.perf_counter()
        eng.search_batched(qv, qls, k)
        cold_after = time.perf_counter() - t0
    return {"warmup_s": rep["seconds"], "programs": rep["programs"],
            "cold_after_warmup_s": cold_after}


def run(n=4_000, k=10, out_dir=None, measure_warmup=True, sweep=True,
        tiny=False):
    if tiny:
        # CI smoke (benchmarks.run --tiny): all four backends end to end
        # at toy size; the cold warmup + the sweep are full-size-only
        n, measure_warmup, sweep = 600, False, False
    if out_dir is None:
        # tiny runs must never clobber the recorded artifact unless the
        # caller routed them somewhere explicitly (CI's --out-dir upload)
        out_dir = tempfile.mkdtemp(prefix="exp9_tiny_") if tiny else "."
    x, ls, qv, qls = make_dataset(n=n, n_labels=12, q=80, seed=7)
    gt_d, gt_i = ground_truth(x, ls, qv, qls, k)
    rows, payload = [], {"n": n, "k": k, "q": len(qls), "backends": {}}
    for backend, params in BACKENDS:
        eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2,
                                      backend=backend, **params)
        modes = measure_modes(eng, qv, qls, k, gt_i, n)
        st = eng.stats()
        payload["backends"][backend] = {
            **modes, "params": params, "n_indexes": st.n_selected,
            "achieved_c": st.achieved_c, "build_seconds": st.build_seconds,
            "nbytes": st.nbytes, "arena_nbytes": st.arena_nbytes,
            "segment_nbytes": st.segment_nbytes,
        }
        if measure_warmup:
            wu = _measure_warmup(backend, params, n, k)
            payload["backends"][backend]["warmup"] = wu
            wu["cold_shrink"] = (modes["batched"]["cold_s"]
                                 / max(wu["cold_after_warmup_s"], 1e-9))
        bat = modes["batched"]
        rows.append({"name": f"exp9/{backend}",
                     "us_per_call": f"{bat['us_per_query_warm']:.1f}",
                     "qps_warm": f"{bat['qps_warm']:.0f}",
                     "qps_cold": f"{bat['qps_cold']:.0f}",
                     "qps_warm_looped": f"{modes['looped']['qps_warm']:.0f}",
                     "speedup_vs_loop": f"{modes['speedup_warm']:.2f}",
                     "recall": f"{bat['recall']:.4f}",
                     "n_indexes": st.n_selected,
                     "achieved_c": f"{st.achieved_c:.3f}"})

    if sweep:
        # selection-size sweep (flat): under the pre-arena executor warm
        # QPS degraded as n_indexes grew (one dispatch per routed group);
        # the segmented executor's launch count is bounded by span tiers
        payload["flat_sweep"] = []
        for c in (0.05, 0.1, 0.2, 0.35, 0.5):
            eng = LabelHybridEngine.build(x, ls, mode="eis", c=c,
                                          backend="flat")
            modes = measure_modes(eng, qv, qls, k, gt_i, n)
            st = eng.stats()
            payload["flat_sweep"].append({
                "c": c, "n_indexes": st.n_selected,
                "qps_warm_batched": modes["batched"]["qps_warm"],
                "qps_warm_looped": modes["looped"]["qps_warm"],
                "speedup_warm": modes["speedup_warm"],
                "nbytes": st.nbytes,
            })
            rows.append({"name": f"exp9/flat_sweep_c={c}",
                         "us_per_call":
                         f"{modes['batched']['us_per_query_warm']:.1f}",
                         "n_indexes": st.n_selected,
                         "qps_warm": f"{modes['batched']['qps_warm']:.0f}",
                         "qps_warm_looped":
                         f"{modes['looped']['qps_warm']:.0f}"})

    # selection identity: same keys regardless of backend
    emit(rows, "exp9")
    emit_json(payload, "exp9", out_dir)
    return rows


if __name__ == "__main__":
    run()
