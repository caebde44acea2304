"""The harness end to end on the CPU at a tiny size, with the MoE kernels
in Pallas interpret mode.  The tests step past the look for a chip (and
take the CPU's peaks from the tiny checkout), and drive everything else
of a run: the cell, configuration, mix, limits and one extra metric are
found as files only.  Broken under the timed path, or replaced by the
float8 control, the run has to come out not correct."""

from __future__ import annotations

import json

import jax
import pytest

from benchmarks.chip import calibrate, check, model, run

import chipbench_tiny as tiny

SEED = 2**31 + 12345
#: the tiny cell's limits, set like the real ones from readings at this
#: size on the CPU: sound runs read loss 3.6e-3, grad 1.0e-2, delta
#: 4.3e-3 on SEED; the float8 control reads grad 0.11-0.18; half a batch
#: reads loss 0.025-0.035; an unchanged state reads delta 1.
LIMITS = {"loss_gap": {"limit": 0.01}, "grad_gap": {"limit": 0.08},
          "delta_gap": {"limit": 0.05}}
EXTRA_METRIC = '''
def read(run):
    return run.steps
'''


@pytest.fixture
def root(tmp_path, monkeypatch):
    r = tiny.make_root(tmp_path, LIMITS)
    (r / "benchmarks/chip/metrics/steps_seen.py").write_text(EXTRA_METRIC)
    bench = json.loads((r / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "steps_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "train loop",
        "moves": "tokens_per_s", "workloads": [tiny.CELL]})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    return r


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_tiny(root, trace: int, seed: int = SEED):
    return run.main(["--workload", tiny.CELL, "--seed", str(seed),
                     "--seconds", "1", "--trace", str(trace)], root=root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_found_by_files_runs_and_is_correct(root, capsys, trace):
    assert run_tiny(root, trace) == 0
    out = last_line(capsys)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["count"] == 1
    if trace:
        assert out["metrics"]["steps_seen"]["value"] == out["attempted"]
        assert out["metrics"]["compiles_in_window"]["value"] == 0
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "tokens_per_s" not in out["metrics"]
    else:
        assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"] == LIMITS[name]["limit"]


def test_refuses_without_a_chip(tmp_path, capsys):
    r = tiny.make_root(tmp_path, LIMITS)
    with pytest.raises(SystemExit) as exc:
        run_tiny(r, 0)
    assert exc.value.code == 2
    assert capsys.readouterr().out == ""


def _unchanged(fn):
    return jax.jit(lambda p, o, b: (p, o, fn(p, o, b)[2]))


def _half_batch(fn):
    return jax.jit(lambda p, o, b: fn(
        p, o, {k: v[: v.shape[0] // 2] for k, v in b.items()}))


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(root, capsys, monkeypatch, fault):
    init = model.Program.__init__

    def broken(self, *a, **k):
        init(self, *a, **k)
        self.step = fault(self.step.__wrapped__)

    monkeypatch.setattr(model.Program, "__init__", broken)
    assert run_tiny(root, 0) == 0
    assert last_line(capsys)["correct"] is False


def test_float8_control_is_not_correct(root):
    """The reference with float8 (e4m3) matmul operands, put in the
    program's place, fails the limits on the seeds used here."""
    from benchmarks.chip import spec
    from benchmarks.chip.reference.common import seed_key
    from benchmarks.chip.traffic import TrafficSource

    bench = spec.Benchmark(root)
    cfg, mix = bench.config("tiny-moe"), bench.traffic("smoke")
    reference = bench.reference("tiny-moe")
    for seed in (1, 2):
        source = TrafficSource(mix, cfg["vocab_size"], seed)
        batches = [source.batch(i) for i in range(3)]
        ref = model.reference_readings(cfg, mix, reference, seed_key(seed),
                                       batches)
        ctl = model.reference_readings(cfg, mix, reference, seed_key(seed),
                                       batches, matmul="float8_e4m3")
        assert not check.verdict(check.gaps(ctl, ref), LIMITS)


def test_calibration_reads_program_control_and_fault(root, tmp_path):
    out = tmp_path / "cal.json"
    assert calibrate.main(["--workload", tiny.CELL, "--seeds", "5",
                           "--control-seeds", "5", "--fault-seeds", "5",
                           "--out", str(out)], root=root) == 0
    rows = json.loads(out.read_text())
    assert [r["kind"] for r in rows] == ["program", "control", "half_batch"]
    assert all(set(check.NUMBERS) <= set(r) for r in rows)
    assert [r["correct"] for r in rows] == [True, False, False]


@pytest.mark.parametrize("rows, seq, want", [(2, 64, (1, 64)),
                                             (1, 64, (1, 32))],
                         ids=["rows", "one_row"])
def test_half_batch_keeps_half(rows, seq, want):
    """The half-batch fault keeps the first half of the rows, or of a
    lone row's tokens."""
    from benchmarks.chip.traffic import TrafficSource

    mix = {**tiny.SMOKE_MIX, "sequences_per_step": rows,
           "sequence_length": seq}
    source = TrafficSource(mix, 200, SEED)
    half = calibrate.HalfBatch(source).batch(3)
    full = source.batch(3)
    for k in ("tokens", "labels"):
        assert half[k].shape == want
        assert (half[k] == full[k][:want[0], :want[1]]).all()

