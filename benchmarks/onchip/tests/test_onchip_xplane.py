"""The XSpace decoder (``xplane_ops``) and the three readers built on it:
``scan_gather_ms`` (device time of the scan's gathers, by named scope),
``call_idle_ms`` (device idle time inside calls, by program span) and
``tier_pack_ms`` (the ``search.pack`` spans).  On the recorded chip trace
(``fixtures/v5e-small.xplane.pb``, a program older than the scopes and
the profiler spans) and on hand-made events."""
from pathlib import Path

import onchip_testkit as kit
import pytest

import harness
import trace_reduce
import xplane_ops

FIXTURE = Path(__file__).resolve().parent / "fixtures"
MS = 1_000_000   # nanoseconds


def _fixture_events():
    return xplane_ops.load(trace_reduce.find_trace(FIXTURE))


def _reader(name):
    return harness.load_reader(kit.ONCHIP, name)


def _ctx(spans=(), calls=()):
    return harness.MetricContext(cfg={}, calls=list(calls),
                                 spans=list(spans), trace={}, peaks={},
                                 member=None)


def test_op_intervals_equal_profile_data():
    import jax
    data = jax.profiler.ProfileData.from_file(
        str(trace_reduce.find_trace(FIXTURE)))
    want = [(trace_reduce.op_name(e.name), e.start_ns, e.duration_ns)
            for plane in data.planes
            if trace_reduce.DEVICE_PLANE.match(plane.name)
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events]
    got = [(op, s, d) for _, op, _, s, d in _fixture_events()["ops"]]
    assert len(got) == 624
    assert got == want
    notes = trace_reduce.load(trace_reduce.find_trace(FIXTURE),
                              harness.ANNOTATED)["annotations"]
    assert [(n, s, d) for n, s, d, _ in _fixture_events()["host"]
            if n in harness.ANNOTATED] == notes


def test_ops_carry_their_name_stack_path():
    ops = _fixture_events()["ops"]
    paths = {tf_op for _, _, tf_op, _, _ in ops}
    assert "jit(_segmented_topk)/gather:" in paths
    assert "jit(_segmented_topk)/while/body/closed_call/gather:" in paths
    scan = [o for o in ops if xplane_ops.program_of(o[2]) == "_segmented_topk"]
    assert scan and all(o[0] == 0 for o in scan)
    # a copy carries no path; no op of this older program carries a scope
    assert ("copy-start", "") in {(o[1], o[2]) for o in ops}
    assert not any(xplane_ops.scope_of(p, ("scan.",)) for p in paths)


def test_scan_gather_ms_reads_none_before_the_scopes(monkeypatch):
    monkeypatch.setattr(xplane_ops, "run_events", _fixture_events)
    ctx = _ctx()
    assert _reader("scan_gather_ms")(ctx) is None
    assert ctx.notes == []


def _scoped_events():
    path = "jit(_segmented_topk)/while/body/closed_call/{}/gather:"
    ops = [  # (device, op, tf_op, start, duration)
        (0, "fusion.1", path.format("scan.row_ids"), 1 * MS, 1 * MS),
        (0, "fusion.2", path.format("scan.rows"), 2 * MS, 4 * MS),
        (0, "fusion.3", path.format("scan.norms"), 6 * MS, 1 * MS),
        (0, "fusion.4", path.format("scan.label_words"), 7 * MS, 1 * MS),
        (0, "fusion.5", path.format("scan.merge"), 8 * MS, 2 * MS),
        (0, "while.1", "jit(_segmented_topk)/while", 1 * MS, 9 * MS),
        (0, "fusion.6", "jit(_merge_topk)/delta.merge/top_k:",
         10 * MS, 1 * MS),
        (0, "fusion.2", path.format("scan.rows"), 22 * MS, 4 * MS),
        (0, "fusion.2", path.format("scan.rows"), 40 * MS, 4 * MS),  # after
    ]
    host = [("search_batched", 0, 20 * MS, 1),
            ("search_batched", 21 * MS, 9 * MS, 1)]
    return {"ops": ops, "host": host}


def test_scan_gather_ms_sums_the_gather_scopes_per_call(monkeypatch):
    monkeypatch.setattr(xplane_ops, "run_events", _scoped_events)
    ctx = _ctx()
    # row ids 1 + rows 4 + 4 + norms 1 + labels 1 ms over two calls; the
    # merge, the loop that holds them, another program and an op past
    # the last call are left out
    assert _reader("scan_gather_ms")(ctx) == pytest.approx(5.5)
    note, = ctx.notes
    for stage in ("scan.row_ids", "scan.rows", "scan.norms",
                  "scan.label_words", "scan.merge"):
        assert stage in note
    assert "delta.merge" not in note and "while" not in note


def _straddling_events():
    host = [  # (name, start, duration, thread)
        ("search_batched", 0, 10 * MS, 1),
        ("search.route", 0, 2 * MS, 1),
        ("search.pack", 2 * MS, 1 * MS, 1),
        ("search.launch", 3 * MS, 1 * MS, 1),
        ("search.sync", 4 * MS, int(5.5 * MS), 1),
        ("search.telemetry", int(9.5 * MS), int(0.5 * MS), 1),
        ("search.route", 4 * MS, 1 * MS, 2),          # another thread
        ("search_batched", 12 * MS, 8 * MS, 1),
        ("search.route", 12 * MS, 2 * MS, 1),
        ("search.launch", 14 * MS, 1 * MS, 1),
        ("search.sync", 15 * MS, 5 * MS, 1),
    ]
    ops = [(0, "fusion.1", "", int(0.5 * MS), int(0.5 * MS)),
           (0, "fusion.2", "", 5 * MS, 4 * MS),
           (0, "fusion.3", "", 13 * MS, 3 * MS)]
    return {"ops": ops, "host": host}


def test_call_idle_ms_splits_a_straddling_gap_by_intersection(monkeypatch):
    """The gap from 9 to 13 ms runs from call 1's sync through its
    telemetry, between the calls, into call 2's routing: by intersection
    0.5 + 0.5 ms in call 1, 2 ms between, 1 ms in call 2 (its middle,
    11 ms, lies between the calls)."""
    monkeypatch.setattr(xplane_ops, "run_events", _straddling_events)
    names = ("search.route", "search.pack", "search.launch", "search.sync",
             "search.telemetry")
    ctx = _ctx(spans=[{"name": n, "dur": 1.0} for n in names])
    # idle in calls: route 0.5 + 1 + 1, pack 1, launch 1, sync 1 + 0.5 +
    # 4, telemetry 0.5 = 10.5 ms over two calls
    assert _reader("call_idle_ms")(ctx) == pytest.approx(5.25)
    split = xplane_ops.idle_split(_straddling_events(),
                                  xplane_ops.calls(_straddling_events(),
                                                   harness.ANNOTATED),
                                  set(names))
    assert {k: v / MS for k, v in split.items()} == pytest.approx({
        "search.route": 2.5, "search.pack": 1.0, "search.launch": 1.0,
        "search.sync": 5.5, "search.telemetry": 0.5, None: 2.0})
    note, = ctx.notes
    for name in names:
        assert name in note
    assert "between calls, per call: 1.0" in note


def test_call_idle_ms_on_the_older_trace_has_no_program_span(monkeypatch):
    monkeypatch.setattr(xplane_ops, "run_events", _fixture_events)
    ctx = _ctx(spans=[{"name": "search.route", "dur": 5.0}])
    red = trace_reduce.reduce(trace_reduce.load(
        trace_reduce.find_trace(FIXTURE), harness.ANNOTATED), n_devices=1)
    inside = _reader("call_idle_ms")(ctx)
    # all idle time of the window, inside the two calls or between them
    note, = ctx.notes
    outside = float(note.rsplit(": ", 1)[1])
    idle_ms = (red["window_s"] - red["busy_s"]) * 1e3
    assert 2 * (inside + outside) == pytest.approx(idle_ms, rel=1e-9)
    assert "search.route 0.0" in note


def test_tier_pack_ms_averages_the_pack_spans_per_call():
    calls = [harness.Call("search", 1.0, queries=8) for _ in range(2)]
    spans = [{"name": "search.pack", "dur": d} for d in (100.0, 300.0,
                                                          200.0)]
    spans.append({"name": "search.route", "dur": 5000.0})
    read = _reader("tier_pack_ms")
    assert read(_ctx(spans=spans, calls=calls)) == pytest.approx(0.3)
    assert read(_ctx(spans=spans[3:], calls=calls)) is None
