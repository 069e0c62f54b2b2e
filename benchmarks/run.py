"""One function per paper table/figure.  Prints ``name,us_per_call,derived``
CSV.  ``python -m benchmarks.run [--only fig6,exp1,...] [--tiny]
[--tiny-only] [--out-dir DIR]``

``--tiny`` shrinks benchmarks that support it (CI smoke: the bench-smoke
job in .github/workflows/ci.yml runs ``--tiny --tiny-only`` so every
tiny-capable benchmark is exercised end to end per PR); without an
explicit ``--out-dir`` a tiny run writes its JSON artifact to a temp dir,
never over the recorded BENCH_*.json.  ``--tiny-only`` restricts the
selection to benchmarks whose ``run`` accepts a ``tiny`` parameter.
``--out-dir`` routes every produced JSON into one directory (the CI job
uploads it as a workflow artifact for PR-to-PR perf eyeballing).
``--trace`` turns on span tracing and writes one Chrome-trace-event file
``TRACE_<name>.json`` per benchmark next to the JSON artifacts; the
tracer is reset between benchmarks so each file covers exactly one run.
``--metrics`` prints the Prometheus text exposition of the process-wide
registry after the last benchmark."""
import argparse
import inspect
import pathlib
import sys
import time
import traceback

from repro.launch import compile_cache
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from . import (exp1_qps_recall, exp2_index_cost, exp3_shard_scaling,
               exp5_distributions, exp6_label_universe, exp7_vs_optimal,
               exp8_adaptive, exp9_backends, exp10_streaming,
               exp11_serving, exp12_durability, exp13_fused_scan,
               fig6_elastic_factor)

ALL = {
    "fig6": fig6_elastic_factor.run,
    "exp1": exp1_qps_recall.run,
    "exp2": exp2_index_cost.run,
    "exp3": exp3_shard_scaling.run,
    "exp5": exp5_distributions.run,
    "exp6": exp6_label_universe.run,
    "exp7": exp7_vs_optimal.run,
    "exp8": exp8_adaptive.run,
    "exp9": exp9_backends.run,
    "exp10": exp10_streaming.run,
    "exp11": exp11_serving.run,
    "exp12": exp12_durability.run,
    "exp13": exp13_fused_scan.run,
}


def tiny_capable(name: str) -> bool:
    return "tiny" in inspect.signature(ALL[name]).parameters


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tiny-only", action="store_true",
                    help="run only benchmarks that support --tiny")
    ap.add_argument("--out-dir", default="",
                    help="directory for JSON artifacts (benchmarks that "
                         "emit one); created if missing")
    ap.add_argument("--trace", action="store_true",
                    help="enable span tracing; write TRACE_<name>.json "
                         "per benchmark into --out-dir (or cwd)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus exposition after all "
                         "benchmarks finish")
    args = ap.parse_args()
    compile_cache.enable()
    names = [n for n in args.only.split(",") if n] or list(ALL)
    if args.tiny_only:
        names = [n for n in names if tiny_capable(n)]
    if args.out_dir:
        pathlib.Path(args.out_dir).mkdir(parents=True, exist_ok=True)
    trace_dir = pathlib.Path(args.out_dir or ".")
    if args.trace:
        obs_trace.enable()
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        t0 = time.time()
        if args.trace:
            obs_trace.reset()
        try:
            params = inspect.signature(ALL[name]).parameters
            kwargs = {}
            if args.tiny and "tiny" in params:
                kwargs["tiny"] = True
            if args.out_dir and "out_dir" in params:
                kwargs["out_dir"] = args.out_dir
            ALL[name](**kwargs)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
        if args.trace:
            path = trace_dir / f"TRACE_{name}.json"
            obs_trace.get_tracer().write(path)
            print(f"# wrote {path}", flush=True)
    if args.metrics:
        print(obs_metrics.render(), flush=True)
    if failed:
        print(f"# FAILED: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
