"""Shared benchmark harness: datasets, timing, recall, CSV emission.

Sizes are scaled to a single CPU core (the paper runs 1M vectors on a
144-thread Xeon); every benchmark keeps the paper's *structure* — same
workloads, same comparisons, same metrics — at reduced N.
"""
from __future__ import annotations

import contextlib
import json
import time
from pathlib import Path

import jax

import numpy as np

from repro.core import (LabelWorkloadConfig, brute_force_filtered,
                        generate_label_sets, generate_query_label_sets,
                        recall_at_k)
from repro.launch import compile_cache
from repro.obs import metrics as obs_metrics


@contextlib.contextmanager
def cold_compiles():
    """Every program the block runs is compiled anew: the in-memory jit
    caches are cleared and the persistent compilation cache is off for the
    block (``compile_cache.off``).  Cold-start measurements run in process
    this way — a child process could not reach the accelerator the parent
    holds."""
    jax.clear_caches()
    with compile_cache.off():
        yield


def latency_percentiles(lat_s: list[float]) -> dict:
    """Exact order-statistic percentiles of a pooled latency sample, in
    ms — the single home of the benchmark quantile convention (serving
    benchmarks pool latencies across reps BEFORE taking percentiles;
    a p99 of a single rep is one order statistic of a small sample)."""
    a = np.asarray(lat_s, dtype=np.float64)
    if a.size == 0:
        return {"p50_ms": None, "p99_ms": None, "mean_ms": None,
                "max_ms": None}
    return {
        "p50_ms": float(np.percentile(a, 50) * 1e3),
        "p99_ms": float(np.percentile(a, 99) * 1e3),
        "mean_ms": float(a.mean() * 1e3),
        "max_ms": float(a.max() * 1e3),
    }


def make_dataset(n=20_000, d=32, n_labels=12, q=200, distribution="zipf",
                 seed=0, mean_set_size=3.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    ls = generate_label_sets(n, LabelWorkloadConfig(
        num_labels=n_labels, distribution=distribution,
        mean_set_size=mean_set_size, seed=seed + 1))
    qv = rng.standard_normal((q, d)).astype(np.float32)
    qls = generate_query_label_sets(ls, q, seed=seed + 2)
    return x, ls, qv, qls


def ground_truth(x, ls, qv, qls, k=10):
    return brute_force_filtered(x, ls, qv, qls, k)


def measure(searcher, qv, qls, k, gt_i, n, repeats=3):
    """(qps, recall, per-query us).  First call warms any jit caches."""
    searcher.search(qv[:4], qls[:4], k)
    t0 = time.perf_counter()
    for _ in range(repeats):
        d, i = searcher.search(qv, qls, k)
    dt = (time.perf_counter() - t0) / repeats
    return (len(qls) / dt, recall_at_k(i, gt_i, n), dt / len(qls) * 1e6)


def measure_modes(eng, qv, qls, k, gt_i, n, repeats=3):
    """Cold/warm QPS for both executors of a LabelHybridEngine.

    Cold = first call of that executor on this engine (routing-table
    warmup plus tracing/compilation of every touched search program not
    already in the process-wide XLA cache — batched runs first, so its
    cold number is the true fresh-engine cost); warm = steady-state mean
    over ``repeats`` — the serving number.  Returns a machine-readable
    dict (see ``emit_json``).
    """
    out = {}
    for mode in ("batched", "looped"):
        fn = getattr(eng, f"search_{mode}")
        t0 = time.perf_counter()
        d, i = fn(qv, qls, k)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(repeats):
            d, i = fn(qv, qls, k)
        warm = (time.perf_counter() - t0) / repeats
        out[mode] = {
            "cold_s": cold, "warm_s": warm,
            "qps_cold": len(qls) / cold, "qps_warm": len(qls) / warm,
            "us_per_query_warm": warm / len(qls) * 1e6,
            "recall": recall_at_k(i, gt_i, n),
        }
    out["speedup_warm"] = (out["looped"]["warm_s"]
                           / max(out["batched"]["warm_s"], 1e-12))
    return out


def emit_json(payload: dict, name: str, out_dir: str | Path = "."):
    """Write ``BENCH_<name>.json`` — the machine-readable perf artifact
    (CI and later sessions diff these to track the perf trajectory).

    A snapshot of the process-wide metrics registry rides along under a
    ``"metrics"`` key (callers can pre-set the key to override), so every
    benchmark artifact carries the elastic-factor / dispatch / recompile
    accounting of the run that produced it.
    """
    payload = dict(payload)
    if obs_metrics.enabled():
        payload.setdefault("metrics", obs_metrics.snapshot())
    path = Path(out_dir) / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}", flush=True)
    return path


def emit(rows: list[dict], name: str):
    """Print one CSV block: name,us_per_call,derived."""
    for r in rows:
        derived = ";".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("name", "us_per_call"))
        print(f"{r.get('name', name)},{r.get('us_per_call', '')},{derived}",
              flush=True)
