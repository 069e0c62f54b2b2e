"""delta_device_share: the device time of the streaming engine's delta
path, its ops under a ``delta.`` scope (the delta scan's ``delta.scan``
inside ``_segmented_topk``, the merge's ``delta.merge`` in
``_merge_topk``; ``xplane_ops`` decodes each op's name-stack path), as a
share of all device time of the ops that start inside the benchmark's
annotated search calls.  None where those calls ran no device op.
Moves ``search_qps``."""

import bisect

import harness
import trace_reduce
import xplane_ops


def read(ctx):
    events = xplane_ops.run_events()
    if events is None:
        return None
    spans = xplane_ops.calls(events, harness.ANNOTATED)
    if not spans:
        return None
    starts = [s for s, _, _ in spans]
    delta = total = 0
    for _, op, tf_op, s, d in events["ops"]:
        if op.startswith(trace_reduce.CONTAINERS):
            continue
        # the call whose start is the latest at or before the op's start
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= spans[i][1]:
            continue
        total += d
        if xplane_ops.scope_of(tf_op, ("delta.",)):
            delta += d
    if not total:
        return None
    ctx.notes.append(f"delta_device_share: delta ops {delta / 1e6!r} ms of "
                     f"{total / 1e6!r} ms of device time inside "
                     f"{len(spans)} search calls")
    return 100.0 * delta / total

