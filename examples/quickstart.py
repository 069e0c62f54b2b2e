"""Quickstart: build an ELI engine over a labelled vector dataset and run
label-hybrid AKNN queries — the paper's core loop in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py [--metrics]

``--metrics`` prints the Prometheus text exposition of the query-path
telemetry registry (elastic factors, dispatch counts, mutation and WAL
accounting) after the walkthrough.
"""
import sys

from repro.core.engine import LabelHybridEngine, brute_force_filtered
from repro.core import recall_at_k
from repro.data.pipeline import VectorLabelDataset
from repro.launch import compile_cache

compile_cache.enable()

# 1. a labelled vector dataset (Zipf label popularity, like the paper §6)
ds = VectorLabelDataset(n=20_000, dim=32, n_labels=12, seed=0)
vectors, label_sets = ds.generate()
queries, query_labels = ds.queries(200)

# 2. fixed-efficiency selection: every query gets an index with elastic
#    factor > 0.2 (EIS greedy, paper Alg 1) over the Flat TPU backend
engine = LabelHybridEngine.build(vectors, label_sets, mode="eis", c=0.2,
                                 backend="flat")
st = engine.stats()
print(f"selected {st.n_selected} indexes, {st.total_entries} entries "
      f"({st.total_entries / st.n:.2f}x data), achieved c={st.achieved_c:.2f}")

# 3. search: each query routes to ONE selected index (max elastic factor)
dists, ids = engine.search(queries, query_labels, k=10)

# 4. verify against exact filtered ground truth
gt_d, gt_i = brute_force_filtered(vectors, label_sets, queries,
                                  query_labels, 10)
print(f"recall@10 = {recall_at_k(ids, gt_i, len(label_sets)):.4f}")

# 5. fixed-space variant: best elastic factor under a 2x space budget
engine2 = LabelHybridEngine.build(vectors, label_sets, mode="sis",
                                  space_budget=2 * len(label_sets),
                                  backend="flat")
st2 = engine2.stats()
print(f"SIS under 2x budget: c*={st2.achieved_c:.3f}, "
      f"{st2.total_entries} entries")

# 6. tiered-precision storage (DESIGN.md §3.8): at scale memory binds
#    before FLOPs.  storage="int8" scans per-row scalar-quantized codes
#    (~2.7x fewer arena bytes/row, recall@10 >= 0.99); "int8+rerank"
#    adds an f32 rerank tier for exact distances at k' = 4k.
engine8 = LabelHybridEngine.build(vectors, label_sets, mode="eis", c=0.2,
                                  backend="flat", storage="int8")
d8, i8 = engine8.search(queries, query_labels, k=10)
st8 = engine8.stats()
print(f"int8 tier: {st8.arena_nbytes / st.arena_nbytes:.2f}x the f32 "
      f"arena bytes, recall@10 = "
      f"{recall_at_k(i8, gt_i, len(label_sets)):.4f}")

# 6b. fused scan kernel (DESIGN.md §3.9, authoring guide in
#     docs/KERNELS.md): the same segmented program with the scan stage
#     fused — gather, distance, filter, and the running top-k in one
#     kernel, tile sizes from the launch/roofline.py model.  Results are
#     bit-identical; the win is cache traffic at scale (BENCH_exp13.json).
engine_f = LabelHybridEngine.build(vectors, label_sets, mode="eis", c=0.2,
                                   backend="flat", fused=True)
df, idf = engine_f.search(queries, query_labels, k=10)
import numpy as np
assert np.array_equal(np.asarray(idf), np.asarray(ids))
print("fused scan kernel: bit-identical ids, see BENCH_exp13.json for QPS")

# 7. streaming mutations (DESIGN.md §3.6): the corpus is rarely static.
#    insert → search → delete → flush, with search always bit-identical
#    to an engine rebuilt from scratch on the surviving rows.
from repro.core import StreamingEngine

stream = StreamingEngine(engine)
arrivals = VectorLabelDataset(n=100, dim=32, n_labels=12, seed=1)
new_vecs, new_labels = arrivals.generate()
ids = stream.insert(new_vecs, new_labels)          # ids continue the stream
dists, got = stream.search(queries[:8], query_labels[:8], k=10)
stream.delete(ids[:50])                            # tombstone half of them
stream.delete([0, 1])                              # and two original rows
dists, got = stream.search(queries[:8], query_labels[:8], k=10)
st3 = stream.stats()
print(f"streaming: {st3.live_rows} live rows, {st3.tombstoned_rows} "
      f"tombstoned, {st3.delta_rows} in the delta "
      f"(arena v{st3.arena_version})")
report = stream.flush()                            # compact: fold + renumber
print(f"flush folded {report['folded_rows']} delta rows, dropped "
      f"{report['dropped_rows']} in {report['seconds']*1e3:.0f} ms "
      f"(vs full rebuild: see BENCH_exp10.json)")

# 8. crash consistency (DESIGN.md §5): wrap the stream in a write-ahead
#    log + snapshots, kill it mid-mutation with an injected fault, and
#    recover — the recovered engine searches bit-identically.
import tempfile
from pathlib import Path

import numpy as np

from repro.core import (DurableStreamingEngine, FaultPlan, InjectedFault,
                        inject, recover)

dur = Path(tempfile.mkdtemp(prefix="quickstart_dur_")) / "engine"
durable = DurableStreamingEngine.build(vectors, label_sets, mode="eis",
                                       c=0.2, backend="flat",
                                       directory=dur)
ids = durable.insert(new_vecs, new_labels)         # logged, THEN applied
durable.delete(ids[:50])
durable.snapshot()                                 # atomic publish + WAL prune
durable.insert(new_vecs[:40] + 1.0, new_labels[:40])  # the tail to replay
want = durable.search(queries[:8], query_labels[:8], k=10)

# simulated kill: the 2nd WAL append after arming dies mid-write,
# leaving a genuinely torn record on disk
with inject(FaultPlan({"wal.append.mid_write": 1})):
    try:
        durable.delete([2, 3])                     # never acknowledged
    except InjectedFault as crash:
        print(f"crashed at {crash.point}; recovering {dur}")
durable.close()

recovered = recover(dur)                           # snapshot + WAL-tail replay
got = recovered.search(queries[:8], query_labels[:8], k=10)
assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))
print(f"recovered at lsn {recovered.wal.lsn}: search bit-identical "
      f"(torn delete correctly dropped)")
recovered.close()

# 9. observability (DESIGN.md §6): everything above was metered — the
#    process-wide registry has been counting searches, elastic factors,
#    mutations, and WAL records the whole time.
if "--metrics" in sys.argv:
    from repro.obs import metrics

    print(metrics.render())
