"""The reduction from a profiler trace to busy time, program time, top
ops and labelled idle gaps: on hand-made events, and on a small trace
recorded on a TPU v5 lite (``fixtures/v5e-small.xplane.pb``: a
2,000 x 128 engine, one ``search_batched`` of 8 queries, then through
the streaming engine one insert of 100 rows, one delete of 100 and one
search of 8, each inside the benchmark's own annotation; the host plane
is trimmed to those annotations' events)."""
from pathlib import Path

import onchip_testkit  # noqa: F401
import pytest

import harness
import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "fixtures"

MS = 1_000_000   # nanoseconds


def _events():
    ops = [  # (op, module, start, duration)
        ("fusion.1", "_segmented_topk", 0 * MS, 4 * MS),
        ("fusion.2", "_segmented_topk", 2 * MS, 4 * MS),   # overlaps
        ("scatter", "scatter_topk_rows", 6 * MS, 1 * MS),
        ("fusion.1", "_segmented_topk", 10 * MS, 2 * MS),
        ("concatenate", "_merge_topk", 12 * MS, 1 * MS),
        ("fusion.1", "_segmented_topk", 30 * MS, 5 * MS),  # past the window
    ]
    modules = [("_segmented_topk", 0, 6 * MS),
               ("scatter_topk_rows", 6 * MS, 1 * MS),
               ("_segmented_topk", 10 * MS, 2 * MS),
               ("_merge_topk", 12 * MS, 1 * MS)]
    notes = [("search_batched", 0, 13 * MS), ("insert", 15 * MS, 3 * MS),
             ("delete", 18 * MS, 2 * MS)]
    return {"devices": [{"ops": ops, "modules": modules}],
            "annotations": notes}


def test_reduce_hand_made_events():
    red = trace_reduce.reduce(_events(), n_devices=1)
    assert red["window_s"] == pytest.approx(0.020)
    # union of op intervals inside [0, 20 ms): 0-7 and 10-13 ms
    assert red["busy_s"] == pytest.approx(0.010)
    assert red["program_s"]["_segmented_topk"] == pytest.approx(0.008)
    assert red["top_ops"][0] == ["_segmented_topk/fusion.1",
                                 pytest.approx(0.006)]
    # idle 7-10 ms (inside search_batched) and 13-20 ms (the middle,
    # 16.5 ms, inside insert), longest first
    assert red["idle_gaps"] == [["insert", pytest.approx(0.007)],
                                ["search_batched", pytest.approx(0.003)]]


def test_reduce_refuses_a_trace_without_annotations_or_devices():
    ev = _events()
    with pytest.raises(ValueError):
        trace_reduce.reduce(dict(ev, annotations=[]), n_devices=1)
    with pytest.raises(ValueError):
        trace_reduce.reduce(ev, n_devices=2)


def test_program_name():
    assert trace_reduce.program_name("jit__segmented_topk(12)") == \
        "_segmented_topk"
    assert trace_reduce.program_name("jit_scatter_topk_rows") == \
        "scatter_topk_rows"


def test_reduce_recorded_chip_trace():
    ev = trace_reduce.load(trace_reduce.find_trace(FIXTURE),
                           harness.ANNOTATED)
    assert [a[0] for a in ev["annotations"]] == [
        "search_batched", "search_batched"]
    assert len(ev["devices"]) == 1
    assert (len(ev["devices"][0]["ops"]), len(ev["devices"][0]["modules"])) \
        == (624, 53)
    assert all(module for _, module, _, _ in ev["devices"][0]["ops"])
    red = trace_reduce.reduce(ev, n_devices=1)
    assert red["busy_s"] == pytest.approx(602.528e-6, rel=1e-6)
    assert red["window_s"] == pytest.approx(3.787948026, rel=1e-6)
    assert [m[0] for m in red["modules"]].count("_merge_topk") == 1
    assert {"_segmented_topk", "_merge_topk"} <= set(red["program_s"])
    assert red["top_ops"] and len(red["top_ops"]) <= 10
    assert {g[0] for g in red["idle_gaps"]} <= set(harness.ANNOTATED) | {
        "between calls"}
    busy = sum(s for _, s in red["top_ops"])
    assert busy > 0
