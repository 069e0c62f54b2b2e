"""scan_gather_ms: device ms per search call in the gathers of the scan
program ``_segmented_topk``: its ops whose name-stack path (the trace's
``tf_op``, read by ``xplane_ops``) lies under ``scan.row_ids``,
``scan.rows``, ``scan.norms``, ``scan.label_words`` or ``rerank.rows``
(the scopes of ``kernels/ops.py``), over the ops that start inside the
benchmark's annotated calls.  Every stage's ms per call goes to the
notes.  None on a trace whose scan carries no ``scan.`` scope (a program
without the scopes).  Moves ``search_qps``."""

import harness
import trace_reduce
import xplane_ops

GATHERS = ("scan.row_ids", "scan.rows", "scan.norms", "scan.label_words",
           "rerank.rows")
PREFIXES = ("scan.", "rerank.", "delta.")


def read(ctx):
    events = xplane_ops.run_events()
    if events is None:
        return None
    spans = xplane_ops.calls(events, harness.ANNOTATED)
    if not spans:
        return None
    stages = xplane_ops.stage_ns(events, trace_reduce.SCAN, PREFIXES,
                                 spans[0][0], max(e for _, e, _ in spans))
    if not any(name.startswith("scan.") for name in stages):
        return None
    per_call = {name: ns / len(spans) / 1e6 for name, ns in stages.items()}
    ctx.notes.append(
        "scan_gather_ms: device ms per call by scope: " +
        ", ".join(f"{name or '(none)'} {ms!r}" for name, ms in
                  sorted(per_call.items(), key=lambda kv: -kv[1])))
    return sum(per_call.get(name, 0.0) for name in GATHERS)
