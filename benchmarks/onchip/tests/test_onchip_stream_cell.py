"""The sliding-window cell ``eli1m-stream.sliding-runbook``: its
configuration and traffic found by name, a run of it at a test's size
that compiles nothing inside the window, and the three readers it adds —
``compaction_ms`` and ``mutation_ms`` (the streaming engine's spans) and
``delta_device_share`` (device time under the delta's scopes) — on
hand-made spans and events and on the recorded chip trace."""
import json
from pathlib import Path

import onchip_testkit as kit
import pytest

import harness
import trace_reduce
import xplane_ops

CELL = "eli1m-stream.sliding-runbook"
READERS = ("compaction_ms", "mutation_ms", "delta_device_share")
FIXTURE = Path(__file__).resolve().parent / "fixtures"
MS = 1_000_000   # nanoseconds


def _reader(name):
    return harness.load_reader(kit.ONCHIP, name)


def _ctx(spans=()):
    return harness.MetricContext(cfg={}, calls=[], spans=list(spans),
                                 trace={}, peaks={}, member=None)


def test_stream_cell_found_by_name():
    cell = harness.load_cell(kit.REPO, CELL)
    cfg = cell["config"]
    assert cfg["name"] == "eli1m-d128-stream" and cfg["engine"] == "stream"
    static = harness.load_cell(kit.REPO, kit.STATIC)["config"]
    for key in ("n_rows", "dim", "n_labels", "label_seed", "zipf_a",
                "mean_labels_per_row", "max_labels_per_row", "selection",
                "c", "index_backend", "storage", "metric", "k"):
        assert cfg[key] == static[key], key
    assert (cfg["max_delta_fraction"], cfg["max_tombstone_fraction"]) == (
        0.02, 0.25)
    assert set(cfg["reduced"]) == {"n_rows", "n_labels", "index_backend"}
    assert cell["traffic"]["step"] == [
        {"op": "insert", "rows": 10000}, {"op": "delete", "rows": 10000},
        {"op": "search", "queries": 256}]
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "search_qps", "device_peak_gb"}
    assert {m["name"] for m in cell["per_layer"]} == set(READERS)
    for name in READERS:
        assert callable(harness.load_reader(cell["bench_dir"], name))
    # and the static cell reports none of them
    assert not set(READERS) & {
        m["name"] for m in harness.load_cell(kit.REPO, kit.STATIC)[
            "per_layer"]}


def test_stream_cell_compiles_nothing_inside_the_window(tmp_path):
    """The cell as the harness runs it, cut to 4,000 rows and steps of
    40 inserts, 40 deletes and 32 queries, so that the 2% delta-fill
    trigger sets off a compaction every second step: no scan, merge or
    assembly program compiles inside the window."""
    root = kit.tiny_root(tmp_path, n_rows=4000)
    path = root / "benchmarks/onchip/traffic/sliding-runbook.json"
    traffic = json.loads(path.read_text())
    traffic["step"] = [{"op": "insert", "rows": 40},
                       {"op": "delete", "rows": 40},
                       {"op": "search", "queries": 32}]
    path.write_text(json.dumps(traffic))
    engines, lines = [], []
    out = kit.run(root, CELL, seed=3160000001, seconds=2.0,
                  fault=engines.append, log=lines.append)
    assert out["correct"] is True, out["checks"]
    assert len(engines[0].compaction_log) >= 2
    window = [line for line in lines if "compiles inside the window" in line]
    assert window == ["[window] compiles inside the window: 0 "
                      "(_segmented_topk, scatter_topk_rows, _merge_topk "
                      "cache growth)"]


def _span(name, ts, dur):
    return {"name": name, "ts": ts, "dur": dur}


def test_compaction_ms_averages_the_compact_spans():
    spans = [_span("stream.insert", 0.0, 1000.0),
             _span("stream.compact", 100.0, 800.0),
             _span("stream.insert", 2000.0, 2500.0),
             _span("stream.compact", 2100.0, 2200.0),
             _span("search.route", 5000.0, 7000.0)]
    read = _reader("compaction_ms")
    assert read(_ctx(spans)) == pytest.approx(1.5)
    assert read(_ctx(spans[4:])) is None


def test_mutation_ms_leaves_out_the_nested_compactions():
    spans = [_span("stream.insert", 0.0, 1000.0),
             _span("stream.compact", 100.0, 800.0),    # inside: left out
             _span("stream.delete", 1500.0, 300.0),
             _span("stream.insert", 2000.0, 500.0),
             _span("stream.compact", 3000.0, 900.0),   # outside every call
             _span("stream.delta_append", 2100.0, 200.0)]
    read = _reader("mutation_ms")
    # (1000 - 800) + 300 + 500 us over three calls
    assert read(_ctx(spans)) == pytest.approx(1.0 / 3)
    assert read(_ctx([spans[1], spans[4]])) is None


def _delta_events():
    scan = "jit(_segmented_topk)/while/body/closed_call/{}/fusion:"
    delta = "jit(_segmented_topk)/delta.scan/while/body/{}/fusion:"
    ops = [  # (device, op, tf_op, start, duration)
        (0, "fusion.1", scan.format("scan.rows"), 1 * MS, 6 * MS),
        (0, "fusion.2", delta.format("scan.rows"), 7 * MS, 1 * MS),
        (0, "while.1", "jit(_segmented_topk)/delta.scan/while", 7 * MS,
         1 * MS),
        (0, "fusion.3", "jit(_merge_topk)/delta.merge/top_k:", 8 * MS,
         1 * MS),
        (0, "scatter.1", "jit(scatter_topk_rows)/scatter", 9 * MS, 2 * MS),
        (0, "fusion.1", scan.format("scan.rows"), 21 * MS, 8 * MS),
        (0, "fusion.2", delta.format("scan.rows"), 40 * MS, 4 * MS),  # out
        (0, "copy.1", "", 15 * MS, 3 * MS),           # between the calls
    ]
    host = [("search_batched", 0, 12 * MS, 1),
            ("insert", 13 * MS, 6 * MS, 1),
            ("search_batched", 20 * MS, 10 * MS, 1)]
    return {"ops": ops, "host": host}


def test_delta_device_share_reads_the_delta_scopes_inside_calls(
        monkeypatch):
    monkeypatch.setattr(xplane_ops, "run_events", _delta_events)
    ctx = _ctx()
    # delta scan 1 + merge 1 of 6 + 1 + 1 + 2 + 8 ms inside the calls; the
    # loop that holds the delta scan, the op between the calls and the op
    # past the last call are left out
    assert _reader("delta_device_share")(ctx) == pytest.approx(
        100.0 * 2 / 18)
    note, = ctx.notes
    assert "2 search calls" in note


def test_delta_device_share_on_a_trace_without_a_delta(monkeypatch):
    monkeypatch.setattr(xplane_ops, "run_events", lambda: xplane_ops.load(
        trace_reduce.find_trace(FIXTURE)))
    assert _reader("delta_device_share")(_ctx()) == 0.0


def test_delta_device_share_reads_none_where_nothing_ran(monkeypatch):
    read = _reader("delta_device_share")
    monkeypatch.setattr(xplane_ops, "run_events", lambda: None)
    assert read(_ctx()) is None
    events = _delta_events()
    events["host"] = [h for h in events["host"] if h[0] == "insert"]
    monkeypatch.setattr(xplane_ops, "run_events", lambda: events)
    assert read(_ctx()) is None
    events = _delta_events()
    events["ops"] = []
    assert read(_ctx()) is None
