"""scan_roofline_share: the least time of the window's base-arena scans
over the device time of the scan program, ``_segmented_topk`` (the module
of the jitted ``kernels/ops.py::_segmented_topk``, whatever scan runs
inside it: XLA's gather or the Pallas ``fused_scan``), from the profiler
trace.

The least time is the benchmark's own count (``workcount.scan_work``):
for each search call, the union of arena rows its routed segments cover,
the segments' row ids, the queries and outputs at the HBM bandwidth, or
``2 * D`` operations per (query, row of its segment) at the bf16 peak,
whichever is longer.  Which of the two binds goes to the run's notes.
Moves ``search_qps``."""

import trace_reduce
import workcount

DTYPE_BYTES = {"float32": 4, "float16": 2, "bfloat16": 2, "int8": 1}


def read(ctx):
    device_s = ctx.trace["program_s"].get(trace_reduce.SCAN, 0.0)
    if device_s <= 0:
        return None
    cfg = ctx.cfg
    least, bounds = 0.0, set()
    for c in ctx.calls:
        if c.op != "search" or not c.cards:
            continue
        routed = [(tuple(workcount.key_labels(key)), n_queries)
                  for key, n_queries, _, _ in c.cards]
        work = workcount.scan_work(
            ctx.member, routed, dim=cfg["dim"],
            dtype_bytes=DTYPE_BYTES[cfg["vector_dtype"]],
            label_words=-(-cfg["n_labels"] // 32), k=cfg["k"])
        t, bound = workcount.least_seconds(work, ctx.peaks)
        least += t
        bounds.add(bound)
    ctx.notes.append(f"scan_roofline_share: least time {least!r} s, bound "
                     f"by {'/'.join(sorted(bounds))}; scan device time "
                     f"{device_s!r} s")
    return 100.0 * least / device_s
