"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration file it names, the engine adapter
``<paths[0]>/engines/<engine>.py`` of the configuration's ``engine``, the
traffic mix ``<paths[0]>/traffic/<traffic>.json`` and each per-layer
metric's reader ``<paths[0]>/metrics/<name>.py``.  This module is the one
general driver they feed: the closed loop, the timing, the check and the
result line.  Adding a cell, a configuration, an engine, a traffic op or a
per-layer metric adds files and entries and edits nothing here.

A traffic mix is a closed loop of one client that repeats ``step``, a list
of calls into the system under test, each ``{"op": <name>, ...}`` with the
op's own parameters.  The engine adapter serves the ops; a step naming an
op that the cell's adapter does not serve is refused.

An engine adapter is a module with

- ``ANNOTATIONS``: each op it serves, mapped to the profiler annotation
  that its calls carry;
- ``ANSWERING``: the ops whose calls answer queries (the check samples
  them, and the per-call readers count their annotations, ``ANNOTATED``);
- ``Engine(cfg, ds, log)``: builds the system under test from the
  configuration and the run's rows (``gen.Dataset``).  The instance has
  ``target``, the system under test (a test's fault receives it);
  ``warm(traffic)``, which compiles every program the traffic reaches;
  ``programs``, the jitted programs (name to function) whose cache growth
  inside the window is logged as compiles; one method per op,
  ``op(spec, index, timed) -> Call``, which prepares the call outside the
  timing and makes the one call into the system through ``timed(fn,
  *args)``, which times it under the op's annotation and returns ``(out,
  seconds)``; ``place(ids)``, the row-stream index of each id an answer
  gives, -1 where it cannot place one; and ``live()``, the rows live now
  as one range ``(lo, hi)`` of the row stream (``gen.Dataset.rows``).

A call that answers queries records its answer and the live range it was
answered over; the check holds it against a float64 reference over
exactly those rows.
"""
from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable

import numpy as np

import gen
import reference
import workcount

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ".jax_cache"      # under the checkout: a fixed path
TRACE_DIR = "benchmarks/onchip/_out/trace"


class CellError(ValueError):
    """The cell, its configuration or its traffic is not well formed."""


def load_cell(root: Path, workload: str) -> dict:
    """The workload entry of ``root/BENCHMARK.json`` with its
    configuration, traffic mix and metric entries resolved by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    bench_dir = root / bench["paths"][0]
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    layers = [m for m in bench["per_layer"] if mine(m)]
    return {"name": workload, "chips": w["chips"], "config": cfg,
            "traffic": traffic, "end_to_end": e2e, "per_layer": layers,
            "bench_dir": bench_dir, "root": root}


def load_reader(bench_dir: Path, name: str) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    import importlib.util
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise CellError(f"no reader for per-layer metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "onchip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_engine(bench_dir: Path, name: str):
    """The engine adapter module ``engines/<name>.py``."""
    import importlib.util
    path = bench_dir / "engines" / f"{name}.py"
    if not path.exists():
        raise CellError(f"no adapter for engine {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "onchip_engine_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def answer_annotations(bench_dir: Path) -> tuple[str, ...]:
    """The annotations of the calls that answer queries, over every engine
    adapter under ``bench_dir/engines``."""
    out = []
    for path in sorted((bench_dir / "engines").glob("*.py")):
        mod = load_engine(bench_dir, path.stem)
        out += [mod.ANNOTATIONS[op] for op in mod.ANSWERING]
    return tuple(dict.fromkeys(out))


def __getattr__(name: str):
    # ``ANNOTATED``, read by the per-call metric readers: the annotations
    # of the calls that answer queries (:func:`answer_annotations`)
    if name == "ANNOTATED":
        return answer_annotations(HERE)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def tpu_devices(chips: int, log: Callable[[str], None]):
    """The first ``chips`` TPU devices, or None (with the reason logged)
    when JAX finds no TPU or too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX finds {devices[0].platform}; the benchmark "
            f"never runs on another platform")
        return None
    if len(devices) < chips:
        log(f"the cell asks for {chips} chips; JAX finds {len(devices)}")
        return None
    return devices[:chips]


@dataclass
class Call:
    op: str
    seconds: float
    queries: int = 0
    answer: dict | None = None  # what the check needs
    cards: list = field(default_factory=list)   # routed groups (traced)


class Driver:
    """Drives one engine adapter (``engine``, an ``Engine`` of
    ``bench_dir/engines/<cfg["engine"]>.py``) with one traffic mix."""

    def __init__(self, cfg: dict, traffic: dict, ds: gen.Dataset, engine,
                 log: Callable[[str], None], bench_dir: Path = HERE):
        self.cfg, self.traffic, self.ds = cfg, traffic, ds
        self.engine, self.log = engine, log
        served = load_engine(bench_dir, cfg["engine"]).ANNOTATIONS
        ops = {s["op"] for s in traffic["step"]}
        if not ops <= set(served):
            raise CellError(f"engine {cfg['engine']!r} serves no traffic "
                            f"ops {sorted(ops - set(served))}")
        self.served = served
        # the annotations of the traffic's calls, which the trace reads
        self.annotations = tuple(dict.fromkeys(served[s["op"]]
                                               for s in traffic["step"]))
        self.n_calls = 0
        self.trace_cards = False

    def call(self, spec: dict) -> Call:
        """One call of the op ``spec["op"]``; an answer's ids are placed in
        the row stream and it records the live range it was answered
        over."""
        op, index = spec["op"], self.n_calls
        self.n_calls += 1
        n_cards = self._cards_len()

        def timed(fn, *args):
            t0 = time.perf_counter()
            with jax_annotation(self.served[op]):
                out = fn(*args)
            return out, time.perf_counter() - t0

        c = getattr(self.engine, op)(spec, index, timed)
        if c.answer is not None:
            c.answer["ids"] = self.engine.place(c.answer["ids"])
            c.answer["live"] = self.engine.live()
        if self.trace_cards:
            c.cards = self._cards_since(n_cards)
        return c

    # -- program counters (traced runs only) --------------------------------
    @staticmethod
    def _tracer():
        from repro.obs import trace as program_trace
        return program_trace.get_tracer()

    def _cards_len(self) -> int:
        return len(self._tracer().cards) if self.trace_cards else 0

    def _cards_since(self, n: int) -> list:
        return [(tuple(c.selected_key), c.n_queries, c.span_tier,
                 c.q_bucket) for c in self._tracer().cards[n:]]

    def warm(self) -> None:
        """Compile every program the traffic can reach (the adapter's
        ``warm``)."""
        self.engine.warm(self.traffic)


def jax_annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def percentile(values, q: float) -> float:
    """Exact order-statistic percentile of a pooled sample (the
    convention of ``benchmarks/common.py::latency_percentiles``)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def check_answers(calls: list[Call], ds: gen.Dataset, traffic: dict,
                  seed: int, k: int, log, answer_fn=None) -> dict:
    """Hold a sample of the window's answers, drawn from the seed among
    the calls that answer queries, against the reference over the rows
    live when each was answered (``reference.compare``): a row inserted
    before the call must be found, a row deleted before it never comes
    back.  ``answer_fn``, called as ``reference.control_topk`` is, puts
    other answers in the program's place (the control)."""
    check = traffic["check"]
    answered = [c for c in calls if c.answer is not None]
    rng = np.random.default_rng([seed, 9])
    n_pick = min(check["searches"], len(answered))
    picked = set(rng.choice(len(answered), size=n_pick, replace=False)
                 .tolist()) if n_pick else set()
    picked.add(len(answered) - 1)       # and the last one
    # one reference over the row stream up to the newest row any picked
    # call saw; ids are row-stream indices, each call's live range a slice
    x, member = ds.rows(0, max(answered[i].answer["live"][1]
                               for i in picked))
    ref = reference.Reference(x, member)
    parts = []
    n_queries = 0
    for i in sorted(picked):
        c = answered[i]
        a = c.answer
        lo, hi = a["live"]
        sel = np.sort(rng.choice(c.queries, size=min(check["queries"],
                                                     c.queries),
                                 replace=False))
        qv = a["qv"][sel]
        qmasks = gen.as_member([a["qls"][j] for j in sel], member.shape[1])
        want_i, want_d = ref.topk(qv, qmasks, k, lo, hi)
        if answer_fn is None:
            got_i, got_d = a["ids"][sel], a["d"][sel]
        else:
            got_i, got_d = answer_fn(x, ref.member, qv, qmasks, k, lo, hi)
        parts.append(reference.compare(ref, qv, qmasks, got_i, got_d, lo,
                                       hi, want_i, want_d))
        n_queries += len(sel)
    numbers = reference.merge(parts)
    log(f"[check] {n_queries} queries of {len(picked)} of {len(answered)} "
        f"calls held against the float64 reference")
    return numbers


class Run:
    """One run of one cell, in the order a run makes them: :meth:`set_up`,
    :meth:`window`, :meth:`release`, :meth:`check`, :meth:`result`.
    ``devices`` are the chips the run may use; ``fault``, a test's hook,
    receives the system under test before the window and may break it."""

    def __init__(self, cell: dict, seed: int, devices,
                 log: Callable[[str], None], fault: Callable | None = None):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.log, self.fault = log, fault
        src = str(cell["root"] / "src")
        if src not in sys.path:
            sys.path.insert(0, src)

    def set_up(self, t_start: float) -> None:
        cfg, log = self.cell["config"], self.log
        self.t_start = t_start
        self.ds = gen.Dataset(cfg, self.seed)
        log(f"[data] {self.ds.n:,} x {cfg['dim']} rows, "
            f"{cfg['n_labels']} labels: "
            f"{time.perf_counter() - t_start:.1f}s since start")
        adapter = load_engine(self.cell["bench_dir"], cfg["engine"])
        t = time.perf_counter()
        engine = adapter.Engine(cfg, self.ds, log)
        log(f"[build] {cfg['engine']} engine: {time.perf_counter() - t:.1f}s")
        self.drv = Driver(cfg, self.cell["traffic"], self.ds, engine, log,
                          self.cell["bench_dir"])
        self.drv.warm()
        if self.fault is not None:
            self.fault(engine.target)

    def window(self, seconds: float, trace: bool = False) -> None:
        """Drive the traffic for ``seconds``, ending on a completed call."""
        import jax
        from repro.obs import trace as program_trace
        drv, steps = self.drv, self.cell["traffic"]["step"]
        programs = drv.engine.programs
        before = sum(f._cache_size() for f in programs.values())
        self.trace = trace
        if trace:
            drv.trace_cards = True
            program_trace.reset()
            program_trace.enable()
            trace_dir = self.cell["root"] / TRACE_DIR
            if trace_dir.exists():
                import shutil
                shutil.rmtree(trace_dir)
            jax.profiler.start_trace(str(trace_dir))
        calls: list[Call] = []
        t_window = time.perf_counter()
        self.setup_s = t_window - self.t_start
        done = False
        while not done:
            for spec in steps:
                calls.append(drv.call(spec))
                if time.perf_counter() - t_window >= seconds:
                    done = True
                    break
        self.window_s = time.perf_counter() - t_window
        self.spans = []
        if trace:
            jax.profiler.stop_trace()
            program_trace.disable()
            self.spans = list(program_trace.get_tracer().events)
        self.calls = calls
        self.peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                        for d in self.devices)
        compiles = sum(f._cache_size() for f in programs.values()) - before
        self.log(f"[window] {self.window_s:.2f}s: {len(calls)} calls, "
                 f"{sum(c.queries for c in calls)} queries")
        self.log(f"[window] compiles inside the window: {compiles} "
                 f"({', '.join(programs)} cache growth)")

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        self.drv.engine = None

    def check(self, answer_fn=None) -> dict:
        return check_answers(self.calls, self.ds, self.cell["traffic"],
                             self.seed, self.cell["config"]["k"], self.log,
                             answer_fn)

    def result(self, numbers: dict) -> dict:
        """The result line's object (the contract's keys, then the checks
        last)."""
        cell, calls = self.cell, self.calls
        correct = reference.within(numbers)
        n_q = sum(c.queries for c in calls)
        out = {"correct": correct, "attempted": n_q, "failed": 0}
        device = {"platform": self.devices[0].platform,
                  "kind": self.devices[0].device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": int(self.peak)}
        if not self.trace:
            values = {
                "setup_s": self.setup_s,
                # queries answered over the window's wall time
                "search_qps": n_q / self.window_s,
                "device_peak_gb": self.peak / 1e9,
            }
            metrics = {}
            for m in cell["end_to_end"]:
                if m["name"] not in values:
                    raise CellError(f"the harness does not measure "
                                    f"end-to-end metric {m['name']!r}")
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        else:
            metrics = self._per_layer(device)
            out["breakdown"] = self.breakdown
        out["metrics"] = metrics
        out["device"] = device
        out["checks"] = {name: {"value": numbers[name], "limit": limit}
                         for name, limit in reference.LIMITS.items()}
        for name, limit in reference.LIMITS.items():
            self.log(f"check {name}: {numbers[name]!r} (limit {limit!r})")
        self.log(f"correct: {correct}")
        return out

    def _per_layer(self, device: dict) -> dict:
        import trace_reduce
        cell = self.cell
        red = trace_reduce.reduce_dir(cell["root"] / TRACE_DIR,
                                      self.drv.annotations,
                                      n_devices=len(self.devices))
        ctx = MetricContext(cfg=cell["config"], calls=self.calls,
                            spans=self.spans, trace=red,
                            peaks=workcount.peaks(
                                self.devices[0].device_kind),
                            member=self.ds.member)
        metrics = {}
        for m in cell["per_layer"]:
            v = load_reader(cell["bench_dir"], m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for line in ctx.notes:
            self.log(line)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        self.breakdown = {"device_ops": red["top_ops"],
                          "idle_gaps": red["idle_gaps"]}
        return metrics


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, devices, log: Callable[[str], None],
             fault: Callable | None = None) -> dict:
    """Set-up, window, check and metrics of one run (:class:`Run`);
    returns the result line's object."""
    run = Run(cell, seed, devices, log, fault)
    run.set_up(t_start)
    run.window(seconds, trace)
    run.release()
    return run.result(run.check())


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile.  It is given through
    ``JAX_COMPILATION_CACHE_DIR``, which JAX reads as it is imported and
    the program's own entry points defer to, so it must come first."""
    if "jax" in sys.modules:
        raise RuntimeError("the compile cache is chosen before JAX loads")
    cache = str(root / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


@dataclass
class MetricContext:
    """What a per-layer metric's reader may read."""
    cfg: dict
    calls: list
    spans: list            # the program's own spans, in the window
    trace: dict            # trace_reduce.reduce_dir's reduction
    peaks: dict            # the chip's row of peaks.json
    member: np.ndarray     # [rows, labels] bool: the rows' label sets
    notes: list = field(default_factory=list)   # lines for stderr


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one on-chip benchmark "
                                             "cell and print its result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    cell = load_cell(ROOT, args.workload)
    cache = use_compile_cache(ROOT)
    devices = tpu_devices(cell["chips"], log)
    if devices is None:
        return 3
    workcount.peaks(devices[0].device_kind)    # unknown chip: an error
    log(f"[setup] {cell['name']} seed {args.seed}: {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                   devices, log)
    print(json.dumps(out), flush=True)
    return 0
