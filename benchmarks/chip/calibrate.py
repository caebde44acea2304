"""Readings that the output limits of a cell are set from, at the cell's
own size, in one process (no measured window: training's readings need
none).

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1 2 3 ... --control-seeds 1 2 3 --fault-seeds 1 2 3

For each of ``--seeds`` the program's compared numbers (the lower
reading); for each of ``--control-seeds`` those of the reference
computed with float8 (e4m3) matmul operands in the program's place (the
control, which has to fail); for each of ``--fault-seeds`` those of the
program trained on half of each batch, the mean taken over the rest.
Prints one JSON line per reading, with the verdict of the cell's
committed limits on it, and writes them all to ``--out``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]


class HalfBatch:
    """A traffic source whose batches keep only their first half of rows:
    the program then takes its mean over the rest."""

    def __init__(self, source):
        self.source = source
        self.tokens_per_step = source.tokens_per_step

    def batch(self, i):
        b = self.source.batch(i)
        half = b["tokens"].shape[0] // 2
        return {k: v[:half] for k, v in b.items()}

    def __iter__(self):
        i = 0
        while True:
            yield self.batch(i)
            i += 1


def program_readings(program, source, key):
    from benchmarks.chip import harness

    params, opt_state, loader, readings = harness.setup(program, source, key)
    loader.close()
    del params, opt_state
    gc.collect()
    return readings


def main(argv=None, root: Path = ROOT) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    for path in (str(root / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from benchmarks.chip import check, harness, model, run, spec
    from benchmarks.chip.reference.common import seed_key
    from benchmarks.chip.traffic import TrafficSource
    from repro.compile_cache import configure_compile_cache

    bench = spec.Benchmark(root)
    cell = bench.cell(args.workload)
    run.require_chips(cell["chips"])
    configure_compile_cache()
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference = bench.reference(cell["config"])
    limits = bench.limits(cell["name"])
    program = model.Program(cfg, mix, reference,
                            bench.adapter(cell["config"]))
    rows = []

    def reference_of(source, key, matmul="float32"):
        batches = [source.batch(i) for i in range(harness.CHECKED_STEPS)]
        return model.reference_readings(cfg, mix, reference, key, batches,
                                        matmul=matmul)

    jobs = ([("program", s) for s in args.seeds]
            + [("control", s) for s in args.control_seeds]
            + [("half_batch", s) for s in args.fault_seeds])
    for kind, seed in jobs:
        key = seed_key(seed)
        source = TrafficSource(mix, program.arch.vocab, seed)
        if kind == "control":
            got = reference_of(source, key, matmul="float8_e4m3")
        else:
            got = program_readings(
                program, HalfBatch(source) if kind == "half_batch" else source,
                key)
        ref = reference_of(source, key)
        numbers = check.gaps(got, ref)
        numbers["correct"] = check.verdict(numbers, limits)
        print(json.dumps({"cell": cell["name"], "kind": kind, "seed": seed,
                          **numbers}), flush=True)
        rows.append({"cell": cell["name"], "kind": kind, "seed": seed,
                     **numbers, "loss": [got["loss"], ref["loss"]],
                     **{f"{r}_leaves": {k: [got[r][k], ref[r][k]]
                                        for k in ref[r]}
                        for r in ("grad", "delta")}})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
