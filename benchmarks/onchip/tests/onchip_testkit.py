"""Shared helpers of the benchmark's CPU tests: a copy of the benchmark's
cells at a size a test run holds, and a driver for one run of it."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ONCHIP = Path(__file__).resolve().parents[1]
REPO = ONCHIP.parents[1]
if str(ONCHIP) not in sys.path:
    sys.path.insert(0, str(ONCHIP))

STATIC = "eli1m-static.paper-mix"


def tiny_root(tmp: Path, n_rows: int = 2000, dim: int = 128) -> Path:
    """A checkout-shaped directory holding the benchmark's own
    ``BENCHMARK.json``, configurations, engine adapters, traffic mixes and
    metric readers, cut to ``n_rows`` rows; the program is the
    repository's ``src``."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    dst = tmp / bench["paths"][0]
    for sub in ("configs", "engines", "traffic", "metrics"):
        shutil.copytree(ONCHIP / sub, dst / sub)
    (tmp / "src").symlink_to(REPO / "src")
    for c in bench["configs"]:
        path = tmp / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(n_rows=n_rows, dim=dim)
        path.write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def cpu_devices():
    import jax
    return jax.devices()[:1]


def run(root: Path, workload: str, seed: int = 7, seconds: float = 1.0,
        fault=None, trace: bool = False, log=None) -> dict:
    """One run of a cell under ``root`` on the CPU (the chip check is
    skipped); returns the result line's object."""
    import harness
    cell = harness.load_cell(root, workload)
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            cpu_devices(), log or (lambda line: None),
                            fault=fault)
