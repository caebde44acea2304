"""A checkout in miniature for the CPU tests: this benchmark's files, a
``BENCHMARK.json`` whose one cell is a tiny OLMoE-shaped model, and a
peaks entry for the CPU.  Everything the cell needs is added as files,
as a later change would add them."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HOME = Path(__file__).resolve().parents[1]
CELL = "tiny-moe.smoke"

TINY_MOE = {
    "hidden_size": 128, "intermediate_size": 64, "num_attention_heads": 2,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 200,
}
#: the tiny adapter runs the MoE kernels' bodies in Pallas interpret mode
INTERPRET = '''

_arch_config = arch_config


def arch_config(cfg, n):
    import dataclasses
    return dataclasses.replace(_arch_config(cfg, n), moe_impl="interpret")
'''
SMOKE_MIX = {"name": "smoke", "sequences_per_step": 2, "sequence_length": 64,
             "microbatch": 1, "token_law": {"kind": "zipf", "a": 1.2},
             "why": "tiny rehearsal"}


def make_root(tmp: Path, limits: dict) -> Path:
    """Copy of the benchmark under ``tmp`` with the tiny cell added."""
    home = tmp / "benchmarks" / "chip"
    shutil.copytree(HOME, home, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    bench = json.loads((HOME.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-moe.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "smoke", "chips": 1, "why": "tiny"})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))

    cfg = json.loads((home / "configs" / "olmoe-1b-7b.json").read_text())
    cfg.update(TINY_MOE, name="tiny-moe")
    cfg["assumed"]["head_dim"]["value"] = 64
    cfg["departures"]["vocab_rows"]["as_run"] = 256
    (home / "configs" / "tiny-moe.json").write_text(json.dumps(cfg))
    (home / "traffic" / "smoke.json").write_text(json.dumps(SMOKE_MIX))
    shutil.copy(home / "reference" / "olmoe-1b-7b.py",
                home / "reference" / "tiny-moe.py")
    (home / "adapters" / "tiny-moe.py").write_text(
        (home / "adapters" / "olmoe-1b-7b.py").read_text() + INTERPRET)
    (home / "limits" / f"{CELL}.json").write_text(json.dumps(limits))
    peaks = json.loads((home / "peaks.json").read_text())
    peaks["cpu"] = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 2**34}
    (home / "peaks.json").write_text(json.dumps(peaks))
    return tmp
