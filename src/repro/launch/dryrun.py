import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# This flag is dry-run-only — smoke tests and benchmarks see 1 device.

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination against the production mesh, prove it fits (memory analysis),
and extract the roofline terms (cost analysis + HLO collective bytes).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results.json
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, input_specs, supports
from repro.parallel.sharding import named
from repro.roofline import collective_bytes_from_hlo, roofline_terms

_COLL_RE = re.compile(
    r"=\s+((?:[a-z0-9]+)\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               donate: bool = True, hlo_out: str | None = None) -> dict:
    cfg = get_config(arch)
    if not supports(cfg, shape_name):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch at 524k context (DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    step, args, specs, donate = input_specs(cfg, shape_name, mesh)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "num_devices": mesh.size}
    with mesh:
        jitted = jax.jit(step, in_shardings=named(mesh, specs),
                         donate_argnums=donate if donate else ())
        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            ),
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes_from_hlo(hlo)
        if hlo_out:
            with open(hlo_out, "w") as f:
                f.write(hlo)
        # XLA-CPU cost_analysis (and the printed HLO) single-counts
        # while-loop bodies.  Correct by the known loop structure: the
        # train step scans microbatches × pattern repeats; prefill scans
        # repeats; decode is unrolled (factor 1).  Approximation noted in
        # EXPERIMENTS.md (ops outside the double scan get over-scaled).
        from repro.launch.specs import SHAPES

        kind = SHAPES[shape_name].kind
        if kind == "train":
            n_micro = max(1, SHAPES[shape_name].global_batch
                          // max(cfg.train_microbatch, 1))
            factor = n_micro * cfg.repeats
        elif kind == "prefill":
            factor = cfg.repeats
        else:
            factor = 1
        rec["scan_correction"] = factor
        # terms from the raw (single-counted) HLO aggregates — a uniform
        # trip multiplier would over-scale non-loop ops, so memory /
        # collective terms are per-loop-iteration LOWER BOUNDS for scanned
        # (train/prefill) shapes and exact for decode (unrolled).
        rec["roofline"] = roofline_terms(
            flops=rec["cost"]["flops"],
            hbm_bytes=rec["cost"]["bytes_accessed"],
            collective_bytes=rec["collectives"]["total_bytes"],
        )
        # corrected compute floor: scan-body flops × trips ≈ true per-step
        # FLOPs (validated ≈ 6·N·D + remat for the dense archs).
        from repro.roofline import PEAK_FLOPS

        rec["roofline"]["compute_s_corrected"] = (
            rec["cost"]["flops"] * factor / PEAK_FLOPS
        )
        rec["status"] = "ok"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--hlo-out", default=None)
    args = ap.parse_args()

    pairs = (
        [(a, s) for a in ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    results = []
    for arch, shape in pairs:
        try:
            rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                             hlo_out=args.hlo_out)
        except Exception as e:  # a failure here is a bug in the system
            rec = {"arch": arch, "shape": shape, "status": "FAILED",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results.append(rec)
        mem = rec.get("memory", {}).get("peak_bytes_per_device", 0) / 2**30
        print(f"[{rec['status']:7s}] {arch:24s} {shape:12s} "
              f"mem/dev={mem:6.2f}GiB "
              f"lower={rec.get('lower_s', 0):6.1f}s "
              f"compile={rec.get('compile_s', 0):6.1f}s "
              + (rec.get("error", "") if rec["status"] == "FAILED" else ""),
              flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    failed = [r for r in results if r["status"] == "FAILED"]
    if failed:
        raise SystemExit(f"{len(failed)} dry-run failures")


if __name__ == "__main__":
    main()
