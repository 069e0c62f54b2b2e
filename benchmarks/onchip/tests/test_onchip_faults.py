"""A run whose timed path is broken underneath comes out not correct.

Each case drives the rest of a run (set-up, window, check, result line)
on the CPU at a small size, with the chip check skipped and one fault
planted in the program after warm-up: an answer altered where the scan
produces it, and half of each batch left out.  The cell serves read-only
(no step carries state) on one chip (no exchange between chips), so
those two faults are the ones it can have."""
import numpy as np
import onchip_testkit as kit
import pytest

from repro.kernels import ops


def _shift_ids(gid, n):
    import jax.numpy as jnp
    return jnp.where(gid < n, (gid + 1) % n, gid)


def alter_scan(monkeypatch):
    real = ops.segmented_topk

    def altered(*args, **kw):
        vals, pos, gid = real(*args, **kw)
        return vals, pos, _shift_ids(gid, args[2].shape[0])

    def plant(target):
        monkeypatch.setattr(ops, "segmented_topk", altered)
    return plant


def half_batch(monkeypatch):
    def plant(target):
        real = target.search_batched

        def first_half(qv, qls, k, **kw):
            d, ids = real(qv, qls, k, **kw)
            d, ids = np.array(d), np.array(ids)
            d[len(qls) // 2:] = np.inf
            ids[len(qls) // 2:] = target.sentinel
            return d, ids
        monkeypatch.setattr(target, "search_batched", first_half)
    return plant


@pytest.mark.parametrize("workload,fault", [
    (kit.STATIC, alter_scan),
    (kit.STATIC, half_batch),
], ids=["static-answer-altered", "static-half-batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, workload,
                                           fault):
    out = kit.run(kit.tiny_root(tmp_path), workload, seconds=2.0,
                  fault=fault(monkeypatch))
    assert out["correct"] is False
    failed = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed
