"""What a JAX profiler trace holds beyond ``jax.profiler.ProfileData``:
each device op's name-stack path, and the host's annotations by thread.

``ProfileData`` gives an event's name and times but none of the stats of
its metadata, and the name stack of an XLA op lives there: every op
event of a ``/device:TPU:<n>`` plane points, by metadata id, at an event
metadata whose ``tf_op`` stat is the op's ``jax.named_scope`` path, e.g.
``jit(_segmented_topk)/while/body/scan.rows/gather``.  The same HLO text
can occur in two programs, so an op is matched to its path by that id and
never by its name.

:func:`load` decodes the XSpace (``.xplane.pb``) with the installed
``google.protobuf``, from a schema built here (the fields of
``tsl/profiler/protobuf/xplane.proto`` that are read), and returns plain
tuples on the profiler's one clock, in nanoseconds from its epoch, as
``ProfileData`` gives them:

- ``ops``: (device, op, tf_op, start, duration) for each event of the
  ``XLA Ops`` line of each device plane; ``op`` is the HLO instruction's
  name (before `` = ``), ``tf_op`` its path ("" where it has none);
- ``host``: (name, start, duration, thread) for each event of each
  ``/host:`` plane line; the thread is the line's id.

On these, :func:`calls` finds the benchmark's own annotation of each
search call, :func:`stage_ns` sums a program's device time by named
scope, and :func:`idle_split` splits the device's idle time by the
program span the host was inside, by interval intersection.
"""
from __future__ import annotations

import functools
from pathlib import Path

import trace_reduce

OPS_LINE = "XLA Ops"
TF_OP = "tf_op"

# (message, ((field, number, type, repeated, message type), ...))
_SCHEMA = (
    ("XSpace", (("planes", 1, "message", True, "XPlane"),)),
    ("XPlane", (("name", 2, "string", False, None),
                ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True,
                 "XPlane.EventMetadataEntry"),
                ("stat_metadata", 5, "message", True,
                 "XPlane.StatMetadataEntry"))),
    ("XPlane.EventMetadataEntry", (("key", 1, "int64", False, None),
                                   ("value", 2, "message", False,
                                    "XEventMetadata"))),
    ("XPlane.StatMetadataEntry", (("key", 1, "int64", False, None),
                                  ("value", 2, "message", False,
                                   "XStatMetadata"))),
    ("XLine", (("id", 1, "int64", False, None),
               ("name", 2, "string", False, None),
               ("timestamp_ns", 3, "int64", False, None),
               ("events", 4, "message", True, "XEvent"))),
    ("XEvent", (("metadata_id", 1, "int64", False, None),
                ("offset_ps", 2, "int64", False, None),
                ("duration_ps", 3, "int64", False, None))),
    ("XStat", (("metadata_id", 1, "int64", False, None),
               ("str_value", 5, "string", False, None),
               ("ref_value", 7, "uint64", False, None))),
    ("XEventMetadata", (("name", 2, "string", False, None),
                        ("stats", 5, "message", True, "XStat"))),
    ("XStatMetadata", (("name", 2, "string", False, None),)),
)


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="onchip_xplane.proto", package="onchip_xplane",
        syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    types = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "string": F.TYPE_STRING, "message": F.TYPE_MESSAGE}
    messages = {}
    for name, fields in _SCHEMA:
        outer, _, inner = name.partition(".")
        if inner:   # a map's entry, nested in its message
            msg = messages[outer].nested_type.add(name=inner)
            msg.options.map_entry = True
        else:
            msg = fd.message_type.add(name=name)
        messages[name] = msg
        for fname, number, ftype, repeated, ref in fields:
            f = msg.field.add(
                name=fname, number=number, type=types[ftype],
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = f".onchip_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("onchip_xplane.XSpace"))


def _stat_str(stat, names) -> str:
    """A stat's string value, direct or by reference to a stat
    metadata's name (how a profiler interns repeated strings)."""
    if stat.str_value:
        return stat.str_value
    return names.get(stat.ref_value, "") if stat.ref_value else ""


def _tf_ops(plane) -> dict:
    """metadata id -> the ``tf_op`` of that event metadata."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    tf_id = [k for k, n in names.items() if n == TF_OP]
    out = {}
    for mid, md in plane.event_metadata.items():
        for st in md.stats:
            if st.metadata_id in tf_id:
                out[mid] = _stat_str(st, names)
    return out


def _start_ns(line, ev) -> int:
    return line.timestamp_ns + ev.offset_ps // 1000


def decode(blob: bytes) -> dict:
    """``ops`` and ``host`` (module docstring) of one XSpace's bytes."""
    space = _xspace_class().FromString(blob)
    ops, host = [], []
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            tf_op = _tf_ops(plane)
            names = {k: v.name for k, v in plane.event_metadata.items()}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend((int(m.group(1)), trace_reduce.op_name(names.get(
                    ev.metadata_id, "")), tf_op.get(ev.metadata_id, ""),
                    _start_ns(line, ev), ev.duration_ps // 1000)
                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            names = {k: v.name for k, v in plane.event_metadata.items()}
            for line in plane.lines:
                host.extend((names.get(ev.metadata_id, ""),
                             _start_ns(line, ev), ev.duration_ps // 1000,
                             line.id) for ev in line.events)
    return {"ops": ops, "host": host}


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> dict:
    return decode(Path(path).read_bytes())


def load(path) -> dict:
    """:func:`decode` of the file at ``path``, once per file version:
    the readers of one run share it."""
    path = Path(path)
    return _load(str(path), path.stat().st_mtime_ns)


def run_events() -> dict | None:
    """The decoded trace of this run (the newest ``.xplane.pb`` under the
    harness's trace directory in this checkout), or None where there is
    none."""
    import harness
    try:
        path = trace_reduce.find_trace(harness.ROOT / harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    return load(path)


def scope_of(tf_op: str, prefixes) -> str | None:
    """The first component of the name-stack path ``tf_op`` that starts
    with one of ``prefixes`` (e.g. ``("scan.", "rerank.")``), or None."""
    for part in tf_op.split("/"):
        if part.startswith(tuple(prefixes)):
            return part.rstrip(":")
    return None


def program_of(tf_op: str) -> str:
    """``jit(_segmented_topk)/while/...`` -> ``_segmented_topk``."""
    head = tf_op.split("/", 1)[0]
    return head[4:-1] if head.startswith("jit(") and head.endswith(")") \
        else head


def calls(events: dict, names) -> list[tuple[int, int, int]]:
    """(start, end, thread) of each host event named in ``names`` (the
    benchmark's annotation of a search call), in time order."""
    return sorted((s, s + d, t) for n, s, d, t in events["host"]
                  if n in names)


def stage_ns(events: dict, program: str, prefixes, lo: int,
             hi: int) -> dict:
    """Device ns of ``program``'s ops that start in [lo, hi), by the
    first name-stack scope starting with one of ``prefixes`` ("" for an
    op under none), averaged over the devices that ran ops.  Container
    ops, whose time is their body's, are left out."""
    out: dict[str, float] = {}
    devices = {o[0] for o in events["ops"]}
    for _, op, tf_op, s, d in events["ops"]:
        if lo <= s < hi and program_of(tf_op) == program \
                and not op.startswith(trace_reduce.CONTAINERS):
            key = scope_of(tf_op, prefixes) or ""
            out[key] = out.get(key, 0.0) + d / len(devices)
    return out


def idle_split(events: dict, call_spans, span_names) -> dict:
    """The device's idle ns, averaged over the devices that ran ops:
    inside the calls (``call_spans``, as :func:`calls` gives them), by the
    innermost host span named in ``span_names`` on the call's thread that
    covers it ("" where none does); and, under None, between the first
    call's start and the last call's end but outside every call."""
    if not call_spans:
        return {}
    spans = [(s, s + d, t) for n, s, d, t in events["host"]
             if n in span_names]
    names = [n for n, _, _, _ in events["host"] if n in span_names]
    lo, hi = call_spans[0][0], max(e for _, e, _ in call_spans)
    devices = sorted({o[0] for o in events["ops"]})
    out: dict[str | None, float] = {}

    def add(key, ns):
        out[key] = out.get(key, 0.0) + ns / len(devices)

    for dev in devices:
        busy = trace_reduce.union((s, s + d) for dv, _, _, s, d
                                  in events["ops"]
                                  if dv == dev and s + d > lo and s < hi)
        edges = [lo] + [x for b in busy for x in b] + [hi]
        idle = [(max(a, lo), min(b, hi)) for a, b in zip(edges[::2],
                                                         edges[1::2])]
        idle = [(a, b) for a, b in idle if b > a]
        in_calls = 0
        for cs, ce, thread in call_spans:
            inner = [i for i, (s, e, t) in enumerate(spans)
                     if t == thread and s < ce and e > cs]
            for a, b in idle:
                a, b = max(a, cs), min(b, ce)
                if b <= a:
                    continue
                in_calls += b - a
                cuts = sorted({a, b} | {x for i in inner for x in spans[i][:2]
                                        if a < x < b})
                for x, y in zip(cuts, cuts[1:]):
                    cover = [i for i in inner
                             if spans[i][0] <= x and y <= spans[i][1]]
                    best = min(cover, default=None,
                               key=lambda i: spans[i][1] - spans[i][0])
                    add("" if best is None else names[best], y - x)
        add(None, sum(b - a for a, b in idle) - in_calls)
    return out
