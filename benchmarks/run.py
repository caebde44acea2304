"""Benchmark harness — one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (benchmarks/common.emit) and
writes one machine-readable ``BENCH_<name>.json`` artifact per suite
(rows + pass/fail + failure text; see benchmarks/common.write_artifact),
which CI uploads.

  PYTHONPATH=src python -m benchmarks.run [--only table2,fig4,...]
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from benchmarks import common
from repro.compile_cache import configure_compile_cache

SUITES = [
    ("table2", "benchmarks.bench_table2_bf_vs_rl"),
    ("table3", "benchmarks.bench_table3_sched_time"),
    ("fig4", "benchmarks.bench_fig4_provisioning"),
    ("fig5", "benchmarks.bench_fig5_cost_methods"),
    ("fig8", "benchmarks.bench_fig8_cost_models"),
    ("fig12", "benchmarks.bench_fig12_pipeline"),
    ("roofline", "benchmarks.bench_roofline"),
    ("kernels", "benchmarks.bench_kernels"),
    ("ps", "benchmarks.bench_ps"),
    ("chaos", "benchmarks.bench_chaos"),
    ("serve", "benchmarks.bench_serve"),
    ("slo", "benchmarks.bench_slo"),
    ("slo-overload", "benchmarks.bench_slo_overload"),
    ("replan", "benchmarks.bench_replan"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    configure_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, module in SUITES:
        if only and name not in only:
            continue
        t0 = time.time()
        common.reset_rows()
        try:
            mod = importlib.import_module(module)
            mod.run()
            common.write_artifact(name, ok=True, seconds=time.time() - t0)
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            err = traceback.format_exc(limit=16)
            common.write_artifact(name, ok=False, error=err,
                                  seconds=time.time() - t0)
            print(f"# {name} FAILED:\n{err}", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
