"""The on-chip benchmark's entry point.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints
its result as the last line of standard output; progress and the numbers
compared with their limits go to standard error.  Exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(t_start=T_START))
