"""Readings that the limits of ``reference.LIMITS`` are set from.

    python3 benchmarks/onchip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <n> ...]

For each seed, in this one process: the cell's set-up and a window of
``--seconds`` (the timed path at the cell's own size and load), then the
compared numbers of the program's answers, and, for the control seeds,
those of the control (``reference.control_topk``) and of a planted fault
(``reference.altered_topk``: each answer's k-th row replaced by the next
nearest passing row) on the same sampled queries.  Prints one JSON line
per reading.  Not part of a benchmark run; it needs the chip, like the
runs it stands for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import harness
import reference


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    cell = harness.load_cell(harness.ROOT, args.workload)
    harness.use_compile_cache(harness.ROOT)
    devices = harness.tpu_devices(cell["chips"], log)
    if devices is None:
        return 3
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = harness.Run(cell, seed, devices, log)
        run.set_up(t0)
        run.window(args.seconds)
        run.release()
        readings = {"program": run.check()}
        if seed in args.control_seeds:
            readings["control"] = run.check(answer_fn=reference.control_topk)
            readings["fault"] = run.check(answer_fn=reference.altered_topk)
        for who, numbers in readings.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "who": who, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
