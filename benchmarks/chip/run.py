"""Runs one cell of the on-chip benchmark once and prints its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each compared number beside its
limit); the compared numbers are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# Run as a script, Python puts this directory first on the path, where
# the benchmark's modules would shadow any of the same name.
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]


def process_age() -> float:
    """Seconds since this process started, from the kernel's records."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def require_chips(n: int):
    """The first ``n`` TPU devices; exits with code 2 where there are
    fewer, and never falls back to another platform."""
    import jax

    try:
        devices = jax.devices("tpu")
    except RuntimeError:
        devices = []
    if len(devices) < n:
        print(f"need {n} TPU chip(s), found {len(devices)}; no result",
              file=sys.stderr)
        raise SystemExit(2)
    return devices[:n]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse(argv)
    for path in (str(root / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from benchmarks.chip import harness, spec
    from repro.compile_cache import configure_compile_cache

    bench = spec.Benchmark(root)
    cell = bench.cell(args.workload)
    print(f"setup_mark imports {process_age():.2f}", file=sys.stderr)
    devices = require_chips(cell["chips"])
    configure_compile_cache()
    result, checks = harness.run_cell(
        bench, cell, seed=args.seed, seconds=args.seconds,
        traced=bool(args.trace), devices=devices, process_age=process_age)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
