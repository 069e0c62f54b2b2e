"""Exp-10 (ISSUE 4 + ISSUE 5): the streaming mutation subsystem under load.

Four measurements land in ``BENCH_exp10.json``:

  * ``fill_sweep`` — warm QPS + recall of ``StreamingEngine.search_batched``
    as the delta arena fills (0% → 20% of the base), against the static
    engine's warm QPS on the same (grown) dataset and against exact
    ground truth over the CURRENT survivors.  The acceptance bar: at 10%
    delta fill warm QPS stays within 1.5× of the static engine
    (``qps_ratio_static`` ≤ 1.5 in inverse form: streaming ≥ static/1.5).
  * ``compaction`` — latency of ``flush()`` (device-side arena fold +
    incremental GroupTable + kept-keys apply_selection) vs a full
    ``LabelHybridEngine.build`` from scratch on the survivors
    (re-grouping, re-selection, host re-upload).  ``speedup_vs_rebuild``
    is the acceptance's "compaction ≫ faster than full rebuild".
  * ``warmup`` — cold-start shrinkage of the FIRST post-insert batch after
    ``StreamingEngine.warmup`` pre-traced the tombstone-fused base, delta
    -scan, and merge programs — measured with every compile cache cleared
    first (``common.cold_compiles``, the exp9 pattern).
  * ``delete_sweep`` (ISSUE 5) — a delete-heavy workload (delete batch →
    search batch, repeated) on PRIVATE-storage backends, lazy tombstones
    (``lazy_deletes=True``, the default: per-index bitmaps through
    ``search_padded(tomb=…)``) vs the PR 4 fold-per-delete path
    (``lazy_deletes=False``: every delete forces a full seeded rebuild at
    the next search).  ``lazy_speedup`` is the acceptance bar: delete
    latency drops from O(build) to O(n/8) host bytes, so lazy must win by
    a wide margin.

``tiny=True`` (the ci_tier1 smoke) shrinks sizes and writes the JSON to a
temp dir (unless the caller routes it with an explicit ``out_dir`` — the
CI bench-smoke job uploads that directory as a workflow artifact) so a
smoke run never clobbers the recorded perf artifact.
"""
import tempfile
import time

import numpy as np

from repro.core import LabelHybridEngine, LabelWorkloadConfig, StreamingEngine
from repro.core import generate_label_sets
from repro.index.base import pow2_bucket

from .common import cold_compiles, emit, emit_json, ground_truth, make_dataset

def insert_pool(m: int, d: int, seed: int = 29):
    """Held-out rows to stream in (same label universe as the base)."""
    rng = np.random.default_rng(seed)
    px = rng.standard_normal((m, d)).astype(np.float32)
    pls = generate_label_sets(m, LabelWorkloadConfig(num_labels=12,
                                                     seed=seed + 1))
    return px, pls


def _delete_heavy_sweep(x, ls, qv, qls, k, backends, batches, batch_rows):
    """Interleaved delete-batch → search-batch loop per private backend,
    lazy tombstones vs fold-per-delete (both warmed before timing; the
    fold mode's warm state is immediately invalidated by the first
    delete, which is exactly the cost being measured)."""
    out = {}
    for backend, params in backends:
        res = {}
        for mode, lazy in (("lazy", True), ("fold_per_delete", False)):
            se = StreamingEngine.build(
                x, ls, mode="eis", c=0.2, backend=backend,
                max_delta_fraction=None, max_tombstone_fraction=None,
                lazy_deletes=lazy, **params)
            se.search_batched(qv, qls, k)            # warm the caches
            remaining = np.random.default_rng(17).permutation(
                len(ls)).astype(np.int64)
            folds_seen = 0
            t0 = time.perf_counter()
            for _ in range(batches):
                batch = remaining[:batch_rows]
                remaining = remaining[batch_rows:]
                se.delete(batch)
                se.search_batched(qv, qls, k)
                # the fold path renumbers survivors at every fold, so
                # future victims must translate through each id_map — an
                # API-visible cost of fold-per-delete the lazy path does
                # not impose (ids stay stable between compactions)
                while folds_seen < len(se.compaction_log):
                    id_map = se.compaction_log[folds_seen]["id_map"]
                    folds_seen += 1
                    remaining = id_map[remaining]
                    remaining = remaining[remaining >= 0]
            dt = time.perf_counter() - t0
            res[mode] = {"seconds": dt,
                         "qps": batches * len(qls) / dt,
                         "deleted_rows": batches * batch_rows}
            assert se.lazy_deletes_active == lazy
            assert se.stats().live_rows == len(ls) - batches * batch_rows
        res["lazy_speedup"] = (res["fold_per_delete"]["seconds"]
                               / max(res["lazy"]["seconds"], 1e-9))
        out[backend] = res
    return out


def _measure_qps(searcher, qv, qls, k, repeats=3):
    searcher.search_batched(qv, qls, k)          # warm the caches
    t0 = time.perf_counter()
    for _ in range(repeats):
        d, i = searcher.search_batched(qv, qls, k)
    warm = (time.perf_counter() - t0) / repeats
    return len(qls) / warm, (d, i)


def _measure_warmup(n: int, k: int, q: int, warm: bool) -> dict:
    with cold_compiles():
        x, ls, qv, qls = make_dataset(n=n, n_labels=12, q=q, seed=7)
        px, pls = insert_pool(n // 10, x.shape[1], seed=29)
        se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                                   max_delta_fraction=None,
                                   max_tombstone_fraction=None,
                                   min_delta_capacity=pow2_bucket(n // 10))
        warmup_s, programs = 0.0, 0
        if warm:
            rep = se.warmup([k], [pow2_bucket(q)])
            warmup_s, programs = rep["seconds"], rep["programs"]
        se.insert(px, pls)                   # first mutation AFTER warmup
        se.delete(np.arange(0, n, 97))
        t0 = time.perf_counter()
        se.search_batched(qv, qls, k, min_bucket=pow2_bucket(q))
        cold_after = time.perf_counter() - t0
    return {"warmup_s": warmup_s, "programs": programs,
            "first_mutated_batch_s": cold_after}


def run(n=4_000, k=10, out_dir=None, measure_warmup=True, tiny=False):
    if tiny:
        n, measure_warmup = 600, True
    if out_dir is None:
        out_dir = tempfile.mkdtemp(prefix="exp10_tiny_") if tiny else "."
    q = 80
    x, ls, qv, qls = make_dataset(n=n, n_labels=12, q=q, seed=7)
    pool_m = n // 5 + 8
    px, pls = insert_pool(pool_m, x.shape[1], seed=29)
    rows, payload = [], {"n": n, "k": k, "q": q, "tiny": tiny,
                         "fill_sweep": [], "deleted": {}, "compaction": {},
                         "delete_sweep": {}}

    # -- fill sweep: streaming (delta pending) vs static on the same rows --
    for fill in (0.0, 0.05, 0.10, 0.20):
        m = int(round(fill * n))
        se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                                   max_delta_fraction=None,
                                   max_tombstone_fraction=None,
                                   min_delta_capacity=pow2_bucket(max(m, 1)))
        if m:
            se.insert(px[:m], pls[:m])
        grown_x = np.concatenate([x, px[:m]])
        grown_ls = list(ls) + list(pls[:m])
        static = LabelHybridEngine.build(grown_x, grown_ls, mode="eis",
                                         c=0.2, backend="flat")
        gt_d, gt_i = ground_truth(grown_x, grown_ls, qv, qls, k)
        qps_stream, (d_s, i_s) = _measure_qps(se, qv, qls, k)
        qps_static, (d_t, i_t) = _measure_qps(static, qv, qls, k)
        from repro.core import recall_at_k
        rec = {"fill": fill, "delta_rows": m,
               "qps_warm_streaming": qps_stream,
               "qps_warm_static": qps_static,
               "static_over_streaming": qps_static / max(qps_stream, 1e-9),
               "recall_streaming": recall_at_k(i_s, gt_i, len(grown_ls)),
               "recall_static": recall_at_k(i_t, gt_i, len(grown_ls))}
        payload["fill_sweep"].append(rec)
        rows.append({"name": f"exp10/fill={fill}",
                     "us_per_call": f"{1e6 / max(qps_stream, 1e-9):.1f}",
                     "qps_warm": f"{qps_stream:.0f}",
                     "qps_warm_static": f"{qps_static:.0f}",
                     "slowdown": f"{rec['static_over_streaming']:.2f}",
                     "recall": f"{rec['recall_streaming']:.4f}"})

    # -- tombstones: 10% deleted, searched through the fused mask ----------
    se = StreamingEngine.build(x, ls, mode="eis", c=0.2, backend="flat",
                               max_delta_fraction=None,
                               max_tombstone_fraction=None)
    rng = np.random.default_rng(31)
    dead = rng.choice(n, n // 10, replace=False)
    se.delete(dead)
    alive = np.setdiff1d(np.arange(n), dead)
    gt_d, gt_i = ground_truth(x[alive], [ls[i] for i in alive], qv, qls, k)
    qps_tomb, (d_s, i_s) = _measure_qps(se, qv, qls, k)
    from repro.core import recall_at_k
    id_back = np.full(n + 1, len(alive), np.int64)
    id_back[alive] = np.arange(len(alive))
    i_mapped = np.where(i_s < n, id_back[np.clip(i_s, 0, n)], len(alive))
    payload["deleted"] = {
        "fraction": 0.10, "qps_warm": qps_tomb,
        "recall": recall_at_k(i_mapped, gt_i, len(alive))}

    # -- compaction vs full rebuild (same survivors + pending inserts) -----
    m = n // 10
    se.insert(px[:m], pls[:m])
    surv_x = np.concatenate([x[alive], px[:m]])
    surv_ls = [ls[i] for i in alive] + list(pls[:m])
    rep = se.flush()
    compact_s = rep["seconds"]
    t0 = time.perf_counter()
    LabelHybridEngine.build(surv_x, surv_ls, mode="eis", c=0.2,
                            backend="flat")
    rebuild_s = time.perf_counter() - t0
    payload["compaction"] = {
        "folded_rows": rep["folded_rows"], "dropped_rows": rep["dropped_rows"],
        "compact_s": compact_s, "full_rebuild_s": rebuild_s,
        "speedup_vs_rebuild": rebuild_s / max(compact_s, 1e-9)}
    rows.append({"name": "exp10/compaction",
                 "us_per_call": f"{compact_s * 1e6:.0f}",
                 "full_rebuild_us": f"{rebuild_s * 1e6:.0f}",
                 "speedup_vs_rebuild":
                 f"{payload['compaction']['speedup_vs_rebuild']:.1f}"})

    # -- delete-heavy: lazy tombstones vs fold-per-delete (ISSUE 5) --------
    # graph is omitted from the timed sweep (its Vamana fold is so slow the
    # comparison is a foregone conclusion — it takes the identical lazy
    # path); ivf exercises the wave-widening mask, distributed the sharded
    # bitmap + collective merge
    sweep_backends = [("ivf", {"nprobe": 8})]
    if not tiny:
        sweep_backends.append(("distributed", {}))
    payload["delete_sweep"] = _delete_heavy_sweep(
        x, ls, qv, qls, k, sweep_backends,
        batches=3 if tiny else 6, batch_rows=max(n // 50, 1))
    for backend, res in payload["delete_sweep"].items():
        rows.append({"name": f"exp10/deletes_{backend}",
                     "us_per_call": f"{1e6 / max(res['lazy']['qps'], 1e-9):.1f}",
                     "qps_lazy": f"{res['lazy']['qps']:.0f}",
                     "qps_fold": f"{res['fold_per_delete']['qps']:.0f}",
                     "lazy_speedup": f"{res['lazy_speedup']:.1f}"})

    # -- warmup: first post-insert batch, compiled cold ---------------------
    if measure_warmup:
        wu = _measure_warmup(n, k, q, warm=True)
        nowu = _measure_warmup(n, k, q, warm=False)
        wu["first_mutated_batch_unwarmed_s"] = nowu["first_mutated_batch_s"]
        wu["cold_shrink"] = (nowu["first_mutated_batch_s"]
                             / max(wu["first_mutated_batch_s"], 1e-9))
        payload["warmup"] = wu
        rows.append({"name": "exp10/warmup",
                     "us_per_call": f"{wu['first_mutated_batch_s']*1e6:.0f}",
                     "unwarmed_us":
                     f"{wu['first_mutated_batch_unwarmed_s']*1e6:.0f}",
                     "cold_shrink": f"{wu['cold_shrink']:.1f}",
                     "programs": wu["programs"]})

    emit(rows, "exp10")
    emit_json(payload, "exp10", out_dir)
    return rows


if __name__ == "__main__":
    run()
