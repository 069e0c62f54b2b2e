"""call_idle_ms: the device's idle ms per search call while the host is
inside the call (the benchmark's own ``search_batched`` annotation), by
interval intersection on the profiler's clock (``xplane_ops``).  The
notes split it by the innermost program span the host was inside
(``search.route``, ``search.pack``, ``search.launch``, ``search.sync``,
``search.telemetry``, or none), and give the idle time between calls,
which is the client's own work.  Moves ``search_qps``."""

import harness
import xplane_ops


def read(ctx):
    events = xplane_ops.run_events()
    if events is None:
        return None
    spans = xplane_ops.calls(events, harness.ANNOTATED)
    if not spans or not events["ops"]:
        return None
    names = {e["name"] for e in ctx.spans}
    split = xplane_ops.idle_split(events, spans, names)
    outside = split.pop(None, 0.0)
    per_call = dict.fromkeys(sorted(names) + [""], 0.0)
    for name, ns in split.items():
        per_call[name] = ns / len(spans) / 1e6
    ctx.notes.append(
        "call_idle_ms: device idle ms per call by program span: " +
        ", ".join(f"{name or '(none)'} {ms!r}"
                  for name, ms in per_call.items()) +
        f"; between calls, per call: {outside / len(spans) / 1e6!r}")
    return sum(per_call.values())
