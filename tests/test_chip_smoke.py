"""CPU rehearsal of ``chip_smoke.py``: its phases at reduced size.

The phases are called directly on the CPU, kernels in the Pallas
interpreter, so their control flow and checks are tested without the
chip; the script itself must refuse to report ``ok`` without a TPU.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, **env):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=300)


def _ok_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if '"ok"' in ln]


class TestRefusal:
    def test_refuses_without_tpu(self):
        out = _run([str(SCRIPT)], ROOT)
        assert out.returncode != 0
        assert not _ok_lines(out.stdout)
        assert "[phase]" not in out.stdout
        assert "no TPU" in out.stderr

    def test_refuses_without_the_repo(self, tmp_path):
        lone = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, lone)
        out = _run([str(lone)], tmp_path)
        assert out.returncode != 0
        assert not _ok_lines(out.stdout)


class TestPhasesOnCpu:
    def test_scheduler_phase(self, smoke):
        r = smoke.phase_scheduler(rounds=10)
        assert r["rel_err"] <= 1e-9
        assert r["device_cost"] == pytest.approx(r["oracle_cost"], rel=1e-9)

    def test_ctr_phase(self, smoke):
        r = smoke.phase_ctr(steps=10, platform="cpu")
        assert r["steps"] == 10
        assert r["tower_platforms"] == ["cpu"]
        assert r["replan_calibrations"] >= 1

    def test_ctr_phase_rejects_wrong_platform(self, smoke):
        with pytest.raises(AssertionError, match="tower on"):
            smoke.phase_ctr(steps=5, platform="tpu")

    def test_kernels_phase_interpret(self, smoke):
        r = smoke.phase_kernels(impl="interpret", full=False)
        assert r["dispatch_max_abs"] == 0.0
        assert max(r.values()) <= 2e-3

    def test_serve_phase(self, smoke):
        r = smoke.phase_serve(reduced=True, platform="cpu", batch=2,
                              prompt_len=16, gen=8)
        assert r["pool_conserved"]
        assert r["continuous_requests"] == 12
        # the CPU takes the jnp gather path, not the kernel
        assert not r["paged_decode_has_tpu_custom_call"]
        assert r["paged_vs_dense_max_abs"] <= r["tolerance"]

    def test_pipeline_phase_on_four_devices(self):
        code = textwrap.dedent(f"""
            import json, sys
            sys.path.insert(0, {str(ROOT)!r})
            sys.path.insert(0, {str(ROOT / "src")!r})
            import chip_smoke
            print(json.dumps(chip_smoke.phase_pipeline(num_stages=4)))
        """)
        out = _run(["-c", code], ROOT,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        assert out.returncode == 0, out.stderr[-2000:]
        r = json.loads(out.stdout.strip().splitlines()[-1])
        assert r["grad_devices"] == 4
        assert r["collective_permutes"] > 0
        assert r["loss_gap"] <= 1e-6
