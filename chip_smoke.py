"""Run the filtered-search store's main path once on a TPU and check it.

    python chip_smoke.py [--seed N]        # one chip: phases a-d
    python chip_smoke.py --four-chips      # the sharded backend on 4 chips

One chip: builds an engine over 1,000,000 x 128 f32 rows (the paper's base
size, labels from its Zipf generator) and runs, in this process, the XLA
scan, the fused Pallas scan on f32 and int8+rerank storage, a streaming
insert/delete/flush round and a serving runtime (``repro.launch.smoke``).
``--four-chips`` runs only the ``distributed`` backend over a 4-device
``data`` mesh, against the host reference and the one-device flat result.

Every result is checked against a NumPy float64 brute force.  Progress goes
to standard output; the last line is one JSON object naming the device.
Exits non-zero, with no such line, on any failed check or when JAX finds
no TPU: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded backend over 4 chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              f"refusing to run on the CPU", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want or (args.four_chips and len(devices) != want):
        # the sharded backend spreads over every local device
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.kernels import ops
    from repro.launch import compile_cache, smoke

    def log(line: str) -> None:
        print(line, flush=True)

    log(f"[setup] {len(devices)} x {devices[0].device_kind} visible, {want} "
        f"used; compile cache {compile_cache.enable()}; Pallas interpret "
        f"mode {ops.default_interpret()}")
    if ops.default_interpret():
        print("chip_smoke: Pallas would run in interpret mode on the chip",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    sizes = smoke.SmokeSizes()
    if args.four_chips:
        smoke.run_sharded(sizes, args.seed, log=log)
    else:
        smoke.run_phases(sizes, args.seed, log=log)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:want]]
    log(f"[done] {time.perf_counter() - t0:.1f}s; peak_bytes_in_use per "
        f"device {peaks}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": want}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
