"""The chip smoke's phases at a tiny size on the CPU (Pallas interpreted).

``chip_smoke.py`` runs ``repro.launch.smoke`` at 1M x 128 on a TPU; these
tests run the same phases and checks at a size the CPU interprets in
seconds, so a broken phase is found before any chip time is spent.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro.launch import smoke

TINY = smoke.SmokeSizes(n=1500, dim=16, n_labels=8, n_queries=16,
                        stream_rows=64, n_requests=6)
REPO = Path(__file__).resolve().parents[1]


def test_smoke_phases_tiny():
    lines: list[str] = []
    out = smoke.run_phases(TINY, seed=0, log=lines.append)
    assert set(out) == {"a", "b_f32", "b_int8+rerank", "c", "d"}
    text = "\n".join(lines)
    assert "XLA segmented scan" in text
    assert "Pallas fused scan (interpreted), storage=f32" in text
    assert "Pallas fused scan (interpreted), storage=int8+rerank" in text
    assert "new segmented traces after warmup: 0" in text


def test_sharded_smoke_tiny():
    out = smoke.run_sharded(TINY, seed=0, log=lambda line: None)
    assert out["ids_equal_flat"] == TINY.n_queries * smoke.K


def test_reference_catches_a_wrong_result():
    """The exactness check is not vacuous: swapping one returned row for a
    farther passing row is caught."""
    x, ls, qv, qls = smoke.make_data(TINY, seed=1)
    ref = smoke.HostReference(x, ls, TINY.n_labels)
    ids, dist = ref.topk(qv, qls, smoke.K)
    assert smoke.exact_mismatches(ref, qv, qls, ids, TINY.n, ids,
                                  dist)[0] == []
    worse = ids.copy()
    q = int(np.argmax((ids >= 0).sum(axis=1)))
    farther = np.setdiff1d(np.flatnonzero(ref.passes(qls[q])), ids[q])
    worse[q, -1] = farther[np.argmax(ref.dist(qv[q], farther))]
    assert smoke.exact_mismatches(ref, qv, qls, worse, TINY.n, ids,
                                  dist)[0] == [q]


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout
