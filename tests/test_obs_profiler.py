"""The search path's measurement as a profiler sees it: the named stages
of the scan program (``jax.named_scope``: ``scan.*``, ``rerank.*``,
``delta.*``), the program spans as profiler annotations on the host
plane while tracing is on, and nothing at all while it is off."""

from __future__ import annotations

import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LabelHybridEngine,
    LabelWorkloadConfig,
    generate_label_sets,
    generate_query_label_sets,
)
from repro.kernels import ops
from repro.obs import metrics, trace

SCAN = ("scan.row_ids", "scan.rows", "scan.norms", "scan.label_words",
        "scan.distance", "scan.merge", "scan.resolve_ids")
RERANK = ("rerank.rows", "rerank.merge")
SEARCH_SPANS = ("search.route", "search.pack", "search.launch",
                "search.sync", "search.telemetry")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(23)
    N, D, Q = 2000, 16, 40
    x = rng.standard_normal((N, D)).astype(np.float32)
    ls = generate_label_sets(N, LabelWorkloadConfig(num_labels=6, seed=3))
    qv = rng.standard_normal((Q, D)).astype(np.float32)
    qls = generate_query_label_sets(ls, Q, seed=4, from_base_fraction=0.75)
    eng = LabelHybridEngine.build(x, ls, mode="eis", c=0.2, backend="flat")
    return dict(eng=eng, qv=qv, qls=qls)


@pytest.fixture
def tracing():
    trace.enable()
    trace.reset()
    yield trace.get_tracer()
    trace.disable()


def _op_names(*, fused=False, dtype="f32", rerank=False) -> str:
    """The ``op_name`` metadata of the compiled scan program: the name
    stack a profiler reports as each device op's ``tf_op``."""
    N, D, W, Q, R, lmax = 512, 8, 1, 8, 700, 256
    f32, i32 = jnp.float32, jnp.int32
    int8 = dtype == "int8"
    args = (jnp.zeros((Q, D), f32), jnp.zeros((Q, W), i32),
            jnp.zeros((N, D), jnp.uint8 if int8 else f32),
            jnp.zeros((N, W), i32), jnp.zeros((N,), f32),
            jnp.zeros((R,), i32), jnp.zeros((Q,), i32),
            jnp.full((Q,), lmax, i32), None,
            jnp.ones((N,), f32) if int8 else None,
            jnp.zeros((N,), f32) if int8 else None,
            jnp.zeros((N, D), f32) if rerank else None,
            jnp.zeros((N,), f32) if rerank else None)
    lowered = ops._segmented_topk.lower(
        *args, k=4, lmax=lmax, chunk=64, metric="l2", backend="ref",
        interpret=True, dtype=dtype, fused=fused, qtile=4 if fused else None)
    return " ".join(re.findall(r'op_name="([^"]*)"',
                               lowered.compile().as_text()))


@pytest.mark.parametrize("variant,names", [
    ({}, SCAN),
    ({"fused": True}, SCAN),
    ({"dtype": "int8", "rerank": True}, SCAN + RERANK),
])
def test_scan_program_carries_each_stage_name(variant, names):
    text = _op_names(**variant)
    for name in names:
        assert f"/{name}/" in text, (variant, name)
    if "rerank" not in variant:
        assert "rerank." not in text


def test_delta_merge_carries_its_name():
    z = jnp.zeros((8, 4), jnp.float32)
    i = jnp.zeros((8, 4), jnp.int32)
    text = ops._merge_topk.lower(z, i, z, i, jnp.int32(0), jnp.int32(9),
                                 k=4).compile().as_text()
    assert 'op_name="jit(_merge_topk)/delta.merge/' in text


def _delta_program_text(scope):
    """The compiled delta-scan program (in place over a 256-row delta)
    under ``scope``, and its optimised HLO with the metadata stripped."""
    N, D, W, Q = 256, 8, 1, 8
    f32, i32 = jnp.float32, jnp.int32
    text = ops._segmented_topk.lower(
        jnp.zeros((Q, D), f32), jnp.zeros((Q, W), i32),
        jnp.zeros((N, D), f32), jnp.zeros((N, W), i32), jnp.zeros((N,), f32),
        None, jnp.zeros((Q,), i32), jnp.full((Q,), N, i32),
        jnp.zeros((N // 8,), jnp.uint8), k=4, lmax=N, chunk=64,
        metric="l2", backend="ref", interpret=True, in_place=True,
        scope=scope).compile().as_text()
    body = text[text.index("\nENTRY") if "\nENTRY" in text else 0:]
    return text, re.sub(r", metadata=\{[^}]*\}", "", body)


def test_delta_scan_stages_carry_the_delta_scope():
    """The delta scan runs the base scan's program under ``delta.scan``:
    every stage of it is told apart from the base scan's in a profile,
    and the scope is metadata only — the optimised program is the same."""
    scoped, scoped_hlo = _delta_program_text("delta.scan")
    plain, plain_hlo = _delta_program_text(None)
    paths = [n for n in re.findall(r'op_name="([^"]*)"', scoped)
             if n.startswith("jit(_segmented_topk)/")]
    assert paths and all(n.startswith("jit(_segmented_topk)/delta.scan/")
                         for n in paths)
    for name in ("scan.rows", "scan.label_words", "scan.merge"):
        assert any(f"/{name}/" in n for n in paths), name
    assert "delta.scan" not in plain
    assert scoped_hlo == plain_hlo


def test_scopes_leave_the_program_alone(data):
    """The scopes are metadata: a search compiles the programs it
    compiled before, one per span tier, and tracing adds none."""
    eng, qv, qls = data["eng"], data["qv"], data["qls"]
    d_ref, i_ref = eng.search_batched(qv, qls, 5)
    before = ops._segmented_topk._cache_size()
    trace.enable()
    try:
        d_tr, i_tr = eng.search_batched(qv, qls, 5)
    finally:
        trace.disable()
    assert ops._segmented_topk._cache_size() == before
    np.testing.assert_array_equal(i_tr, i_ref)
    np.testing.assert_array_equal(d_tr, d_ref)


def _host_events(trace_dir):
    from pathlib import Path
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_search_spans_land_on_the_profiler_host_plane(data, tracing,
                                                      tmp_path):
    eng, qv, qls = data["eng"], data["qv"], data["qls"]
    eng.search_batched(qv, qls, 5)      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer_call"):
            eng.search_batched(qv, qls, 5)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    outer = [(s, e) for n, s, e in events if n == "outer_call"]
    assert len(outer) == 1
    lo, hi = outer[0]
    for name in SEARCH_SPANS:
        inside = [(s, e) for n, s, e in events if n == name]
        assert inside, name
        assert all(lo <= s <= e <= hi for s, e in inside), name
    # one launch per span tier, each tier packed before its launch
    launches = [s for n, s, _ in events if n == "search.launch"]
    packs = [s for n, s, _ in events if n == "search.pack"]
    assert len(packs) == len(launches) + 1    # the partition, then tiers
    # the Chrome-trace export still holds the same spans
    assert {e["name"] for e in tracing.events} >= set(SEARCH_SPANS)
    assert "search.dispatch" not in {e["name"] for e in tracing.events}


def test_tracing_off_builds_no_annotation(data, monkeypatch):
    built = []

    class Counting:
        def __init__(self, name):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert not trace.enabled()
    assert trace.span("search.route", Q=3) is trace._NULL
    data["eng"].search_batched(data["qv"], data["qls"], 5)
    assert built == []
    trace.enable()
    try:
        with trace.span("probe"):
            pass
    finally:
        trace.disable()
    assert built == ["probe"]


def test_stage_seconds_split_route_pack_launch_sync(data):
    assert metrics.enabled()
    fam = metrics.REGISTRY.get("eli_search_stage_seconds")
    counts = {s: fam.labels(s).count for s in ("route", "pack", "launch",
                                                "sync")}
    data["eng"].search_batched(data["qv"], data["qls"], 5)
    for stage, n in counts.items():
        assert fam.labels(stage).count == n + 1, stage
    assert 'stage="dispatch"' not in metrics.render()


def test_obs_imports_no_jax_with_tracing_off():
    code = ("import sys\n"
            "from repro.obs import trace\n"
            "with trace.span('search.route'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    subprocess.run([sys.executable, "-c", code], check=True)
