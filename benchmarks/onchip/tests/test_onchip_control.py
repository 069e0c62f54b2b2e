"""The control: the reference put in the program's place at three
bfloat16 passes (``Precision.HIGH``, the step below the configuration's
float32) fails the comparison that the program's own answers pass, at
the cells' widths and a size a test run holds."""
import time

import onchip_testkit as kit
import pytest

import harness
import reference


@pytest.mark.parametrize("workload", [kit.STATIC])
def test_control_is_not_correct(tmp_path, workload):
    cell = harness.load_cell(kit.tiny_root(tmp_path, n_rows=20_000),
                             workload)
    run = harness.Run(cell, 3000000031, kit.cpu_devices(), lambda line: None)
    run.set_up(time.perf_counter())
    run.window(1.0)
    run.release()
    program = run.check()
    control = run.check(answer_fn=reference.control_topk)
    assert reference.within(program), program
    assert not reference.within(control), control
    assert control["dist_err"] > 3 * program["dist_err"]
