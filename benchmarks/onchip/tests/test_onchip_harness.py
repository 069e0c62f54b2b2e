"""The harness finds its cells by name, prints the contract's keys and
refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import onchip_testkit as kit

import pytest

import harness


def test_cells_found_by_name():
    static = harness.load_cell(kit.REPO, kit.STATIC)
    assert static["config"]["name"] == "eli1m-d128-static"
    assert [s["op"] for s in static["traffic"]["step"]] == ["search"]
    assert {m["name"] for m in static["end_to_end"]} == {
        "setup_s", "search_qps", "device_peak_gb"}
    layers = {m["name"] for m in static["per_layer"]}
    assert "route_ms" in layers
    for m in static["per_layer"]:
        assert callable(harness.load_reader(static["bench_dir"], m["name"]))


def test_a_reader_is_found_by_its_exact_name(tmp_path):
    root = kit.tiny_root(tmp_path)
    bench_dir = root / "benchmarks/onchip"
    with pytest.raises(harness.CellError):
        harness.load_reader(bench_dir, "route_ms.variant")
    (bench_dir / "metrics/route_ms.variant.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    assert harness.load_reader(bench_dir, "route_ms.variant")(None) == 1.5


def test_fixture_cell_added_without_editing_the_harness(tmp_path):
    root = kit.tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / bench["configs"][0]["file"]).read_text())
    cfg["name"] = "fixture-config"
    (root / "benchmarks/onchip/configs/fixture-config.json").write_text(
        json.dumps(cfg))
    (root / "benchmarks/onchip/traffic/fixture-mix.json").write_text(
        json.dumps({"step": [{"op": "search", "queries": 8}],
                    "check": {"searches": 1, "queries": 4}}))
    (root / "benchmarks/onchip/metrics/fixture_calls.py").write_text(
        "def read(ctx):\n    return float(len(ctx.calls))\n")
    bench["configs"].append(dict(bench["configs"][0], name="fixture-config",
                                 file="benchmarks/onchip/configs/"
                                      "fixture-config.json"))
    bench["workloads"].append({"name": "fixture.cell",
                               "config": "fixture-config",
                               "traffic": "fixture-mix", "chips": 1,
                               "why": "a fixture"})
    # the end-to-end metrics name no workloads: every cell reports them
    bench["per_layer"].append({"name": "fixture_calls", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "fixture", "moves": "search_qps",
                               "workloads": ["fixture.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(root, "fixture.cell")
    assert cell["traffic"]["step"][0]["queries"] == 8
    assert "fixture_calls" in {m["name"] for m in cell["per_layer"]}
    read = harness.load_reader(cell["bench_dir"], "fixture_calls")
    ctx = harness.MetricContext(cfg=cfg, calls=[1, 2], spans=[], trace={},
                                peaks={}, member=None)
    assert read(ctx) == 2.0
    out = kit.run(root, "fixture.cell", seconds=0.5)
    assert out["correct"] and out["metrics"]["search_qps"]["value"] > 0


def test_result_line_holds_the_contract_keys(tmp_path):
    out = kit.run(kit.tiny_root(tmp_path), kit.STATIC)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(out["metrics"]) == {"setup_s", "search_qps", "device_peak_gb"}
    for name, m in out["metrics"].items():
        assert set(m) == {"value", "unit"}
    assert set(out["checks"]) == {"bad_queries", "rank_gap", "dist_err"}
    json.dumps(out)


def test_a_traffic_op_it_does_not_know_is_refused(tmp_path):
    root = kit.tiny_root(tmp_path)
    cell = harness.load_cell(root, kit.STATIC)
    traffic = dict(cell["traffic"], step=[{"op": "insert", "rows": 5}])
    with pytest.raises(harness.CellError):
        harness.Driver(cell["config"], traffic, None, None, print)


def test_harness_names_no_engine_and_no_op(tmp_path):
    import ast
    names = set()
    for path in (kit.ONCHIP / "engines").glob("*.py"):
        mod = harness.load_engine(kit.ONCHIP, path.stem)
        names |= {path.stem} | set(mod.ANNOTATIONS) | set(
            mod.ANNOTATIONS.values())
    assert {"static", "search", "search_batched"} <= names
    tree = ast.parse((kit.ONCHIP / "harness.py").read_text())
    docs = {id(node.body[0].value) for node in ast.walk(tree)
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef))
            and ast.get_docstring(node) is not None}
    literals = {node.value for node in ast.walk(tree)
                if isinstance(node, ast.Constant)
                and isinstance(node.value, str) and id(node) not in docs}
    assert not literals & names
    assert harness.ANNOTATED == ("search_batched",)
    # an engine is found by its adapter's file, and one without is refused
    root = kit.tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    path = root / bench["configs"][0]["file"]
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cfg, engine="no-such-engine")))
    with pytest.raises(harness.CellError, match="no adapter"):
        kit.run(root, kit.STATIC)


def test_nothing_compiles_inside_the_window(tmp_path):
    lines = []
    out = kit.run(kit.tiny_root(tmp_path), kit.STATIC, seconds=2.0,
                  log=lines.append)
    assert out["correct"] is True
    window = [line for line in lines if "compiles inside the window" in line]
    assert window == ["[window] compiles inside the window: 0 "
                      "(_segmented_topk cache growth)"]
    calls = [line for line in lines if line.startswith("[window] ")]
    assert " 0 calls" not in calls[0]


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    proc = subprocess.run(
        [sys.executable, str(kit.ONCHIP / "run.py"), "--workload",
         kit.STATIC, "--seed", "3000000007", "--seconds", "1", "--trace",
         "0"], cwd=kit.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
