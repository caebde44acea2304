"""One run of one cell: set-up, the measured window, then the check.

Set-up makes the weights on the device from the seed, builds the
program's jitted train step and drives it through its first steps on the
mix's first batches (the three checked steps and one more), through the
same call and feed that the window uses.  The window then runs whole
steps, in the shape of the program's training loop (next batch, step,
wait for the loss), until ``seconds`` have passed.  After it, the peak
memory is read, the program's state is freed and the plain reference
trains the same three steps from the same seed, so that ``check``
can compare the two.
"""

from __future__ import annotations

import gc
import math
import sys
import time
from dataclasses import dataclass, field

import jax

from benchmarks.chip import check, model, trace
from benchmarks.chip.flops import train_flops_per_token
from benchmarks.chip.reference.common import seed_key
from benchmarks.chip.traffic import TrafficSource

#: steps whose readings are compared with the reference
CHECKED_STEPS = 3
#: further set-up steps before the window
WARMUP_STEPS = 1
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Run:
    """What the metric readers read."""

    cell: dict
    cfg: dict
    mix: dict
    peaks: dict
    chips: int
    flops_per_token: float
    setup_s: float = 0.0
    steps: int = 0
    failed: int = 0
    tokens: int = 0
    window_s: float = 0.0
    input_wait_s: float = 0.0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    trace: trace.Reduced | None = None
    readings: dict = field(default_factory=dict)


class _CompileLog:
    """Times at which JAX built an executable (compiled or loaded one
    from the persistent cache)."""

    def __init__(self):
        self.times: list[float] = []

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def memory_stat(device, name: str) -> int:
    """One of the runtime's memory statistics; 0 where the backend keeps
    none (the CPU)."""
    return (device.memory_stats() or {}).get(name, 0)


def setup(program, source, key):
    """Initial state and the checked steps.  Returns the state after the
    set-up steps, the loader, and the program's readings."""
    from repro.data import PrefetchLoader

    loader = PrefetchLoader(source, depth=2)
    params, opt_state = program.init_state(key)
    readings = {"loss": []}
    for i in range(CHECKED_STEPS + WARMUP_STEPS):
        params, opt_state, m = program.step(params, opt_state, next(loader))
        loss = float(m["loss"])
        if i < CHECKED_STEPS:
            readings["loss"].append(loss)
        if i == 0:
            readings["grad"] = program.grad_norms(opt_state)
        if i == CHECKED_STEPS - 1:
            readings["delta"] = program.delta_norms(params, key)
    return params, opt_state, loader, readings


def window(program, params, opt_state, loader, seconds, run: Run):
    """Whole steps until ``seconds`` have passed; the rate counts every
    step and the time from the window's start to the last completion."""
    ann = jax.profiler.TraceAnnotation
    wait = 0.0
    steps = failed = 0
    with ann(trace.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            tb = time.perf_counter()
            with ann("bench.next_batch"):
                batch = next(loader)
            wait += time.perf_counter() - tb
            with ann("bench.dispatch"):
                params, opt_state, m = program.step(params, opt_state, batch)
            with ann("bench.sync"):
                loss = float(m["loss"])
            steps += 1
            failed += not math.isfinite(loss)
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    run.steps, run.failed = steps, failed
    run.window_s, run.input_wait_s = t1 - t0, wait
    return params, opt_state, (t0, t1)


def run_cell(bench, cell: dict, *, seed: int, seconds: float, traced: bool,
             devices, process_age) -> tuple[dict, dict]:
    """Runs the cell once.  Returns the result object and the compared
    numbers with their limits."""
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    reference = bench.reference(cell["config"])
    limits = bench.limits(cell["name"])
    run = Run(cell=cell, cfg=cfg, mix=mix,
              peaks=bench.peaks(devices[0].device_kind), chips=len(devices),
              flops_per_token=train_flops_per_token(
                  reference, reference.dims(cfg), mix["sequence_length"]))

    with _CompileLog() as compiles:
        program = model.Program(cfg, mix, reference,
                                bench.adapter(cell["config"]))
        source = TrafficSource(mix, program.arch.vocab, seed)
        key = seed_key(seed)
        params, opt_state, loader, run.readings = setup(program, source, key)
        # what set-up left on the heap stays out of the collector's
        # passes inside the window
        gc.collect()
        gc.freeze()
        run.setup_s = process_age()
        box: list = []
        try:
            if traced:
                with trace.capture(box):
                    params, opt_state, (t0, t1) = window(
                        program, params, opt_state, loader, seconds, run)
            else:
                params, opt_state, (t0, t1) = window(
                    program, params, opt_state, loader, seconds, run)
        finally:
            loader.close()
        run.compiles_in_window = sum(t0 <= t <= t1 for t in compiles.times)
    run.tokens = run.steps * source.tokens_per_step
    run.memory_peak_bytes = max(memory_stat(d, "peak_bytes_in_use")
                                for d in devices)
    reserved = max(memory_stat(d, "peak_bytes_reserved") for d in devices)
    print(f"peak_bytes_in_use {run.memory_peak_bytes} "
          f"peak_bytes_reserved {reserved}", file=sys.stderr)
    del params, opt_state
    gc.unfreeze()
    gc.collect()
    if box:
        run.trace = trace.reduce(box.pop())

    t_ref = time.perf_counter()
    ref = model.reference_readings(cfg, mix, reference, key,
                                   [source.batch(i) for i in range(CHECKED_STEPS)])
    print(f"reference_s {time.perf_counter() - t_ref:.1f}", file=sys.stderr)
    numbers = check.gaps(run.readings, ref)
    correct = check.verdict(numbers, limits) and run.failed == 0

    metrics = {}
    for m in bench.metrics(cell["name"], traced):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": run.steps,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    checks = {k: {"value": numbers[k], "limit": limits[k]["limit"]}
              for k in check.NUMBERS}
    result["checks"] = checks
    return result, checks
