"""From a JAX profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, on the profiler's one clock (nanoseconds from the
start of the trace):

- device ops: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane
  (an event's name is its HLO instruction; the op is the name before
  `` = ``), each given the module whose execution holds its start;
- device modules: the ``XLA Modules`` line of the same planes, one event
  per execution of a compiled program;
- annotations: the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  around each call into the program, from the host planes.

A program is named by its jitted function: module ``jit__segmented_topk``
is ``_segmented_topk`` (:func:`program_name`).

:func:`reduce` turns these into the device's busy time (the union of op
intervals), the traced window (first annotation start to last annotation
end), device time per program, the top device ops, and the longest idle
gaps, each labelled with the annotation the host was inside at the gap's
middle (``between calls`` when none).
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
IDLE_MIN_NS = 1_000          # gaps shorter than this are not listed
# ops whose time is their body's ops' time, listed apart in the trace
CONTAINERS = ("while", "conditional", "call")


def program_name(module: str) -> str:
    """``jit__segmented_topk(123)`` -> ``_segmented_topk``."""
    name = re.sub(r"\(.*\)$", "", module.strip())
    return name[4:] if name.startswith("jit_") else name


SCAN = "_segmented_topk"


def op_name(hlo: str) -> str:
    """``%fusion.31 = f32[...] fusion(...)`` -> ``fusion.31``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def attribute(ops, modules):
    """(op, module, start, duration) for each (op, start, duration): the
    module is the program whose execution holds the op's start ("" if
    none does).  ``modules`` are sorted by start."""
    out, j = [], 0
    for name, s, d in sorted(ops, key=lambda o: o[1]):
        while j + 1 < len(modules) and modules[j + 1][1] <= s:
            j += 1
        inside = modules and modules[j][1] <= s < modules[j][1] + modules[j][2]
        out.append((name, modules[j][0] if inside else "", s, d))
    return out


def find_trace(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: Path, annotations) -> dict:
    """The events :func:`reduce` reads, as plain lists of tuples."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    devices, notes = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_name(e.name), e.start_ns, e.duration_ns)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((program_name(e.name), e.start_ns,
                                    e.duration_ns) for e in line.events)
            modules.sort(key=lambda mod: mod[1])
            devices[int(m.group(1))] = {"ops": attribute(ops, modules),
                                        "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                notes.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in annotations)
    return {"devices": [devices[i] for i in sorted(devices)],
            "annotations": sorted(notes, key=lambda a: a[1])}


def union(intervals) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, sorted."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(events: dict, n_devices: int, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the first ``n_devices``
    device planes), device seconds per program, the modules in order, the
    ``top`` device ops by time and the ``top`` longest idle gaps."""
    notes = events["annotations"]
    if not notes:
        raise ValueError("the trace holds none of the benchmark's "
                         "annotations")
    lo = min(s for _, s, _ in notes)
    hi = max(s + d for _, s, d in notes)
    devs = events["devices"][:n_devices]
    if len(devs) < n_devices:
        raise ValueError(f"the trace holds {len(devs)} device planes, the "
                         f"run used {n_devices}")
    busy_ns, per_program, per_op, modules, gaps = 0.0, {}, {}, [], []
    for i, dev in enumerate(devs):
        spans = clip(union((s, s + d) for _, _, s, d in dev["ops"]), lo, hi)
        busy_ns += sum(e - s for s, e in spans)
        for name, module, s, d in dev["ops"]:
            if lo <= s < hi and not name.startswith(CONTAINERS):
                key = f"{module}/{name}" if module else name
                per_op[key] = per_op.get(key, 0.0) + d
        for name, s, d in dev["modules"]:
            if lo <= s < hi:
                per_program[name] = per_program.get(name, 0.0) + d / n_devices
                if i == 0:
                    modules.append((name, s * 1e-9, d * 1e-9))
        if i == 0:
            edges = [lo] + [x for se in spans for x in se] + [hi]
            gaps = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2])
                           if e - s >= IDLE_MIN_NS),
                          key=lambda g: g[0] - g[1])[:top]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / n_devices * 1e-9,
        "program_s": {k: v * 1e-9 for k, v in per_program.items()},
        "modules": sorted(modules, key=lambda m: m[1]),
        "top_ops": [[name, ns / n_devices * 1e-9] for name, ns in ops],
        "idle_gaps": [[_label(notes, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def _label(notes, t: float) -> str:
    """The innermost annotation covering time ``t``."""
    best = None
    for name, s, d in notes:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "between calls"


def reduce_dir(trace_dir: Path, annotations, n_devices: int) -> dict:
    return reduce(load(find_trace(trace_dir), annotations), n_devices)
