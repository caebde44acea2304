"""The program's named scopes as the benchmark reads them: the costly
instructions of the compiled step resolve to a scope and a phase, the
scopes leave the compiled step as it is, the readers of the per-layer
metrics built on them read hand-made runs right, and a small trace
recorded on a TPU v5e (``testdata/trace_probe_scoped.*``: the recipe of
``testdata/trace_probe.xplane.pb.gz`` — three steps of a 1-layer MoE
decoder, d_model 256, 8 experts top-2, batch 2 × 256, microbatch 1 —
with the program's scopes, its batches through ``PrefetchLoader`` and
one explicit collection in the window, beside the text of the very
executable that ran) resolves to them."""

from __future__ import annotations

import contextlib
import gzip
import hashlib
import importlib
import json
import re
import shutil
from collections import Counter
from pathlib import Path

import jax
import pytest

from benchmarks.chip import model, opscope, spec, trace
from benchmarks.chip.harness import Run
from benchmarks.chip.reference.common import seed_key
from benchmarks.chip.traffic import TrafficSource
from repro.obs import scopes

import chipbench_tiny as tiny

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
#: the per-layer metrics read from the program's scopes
NAMED_METRICS = ["attention_device_ms", "ffn_device_ms",
                 "moe_routing_device_ms", "optimizer_device_ms",
                 "recompute_device_ms"]
#: opcodes that move no data: the step's structure, its loops' carries,
#: and views
FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
        "while", "conditional", "call", "after-all", "opt-barrier"}
#: op_names outside every scope that are the step's own plumbing: the
#: scans' loop control, slicing and stacking, their carries (the zeros of
#: a transposed scan, the layer scan's sum of MoE aux losses), and the
#: checkpoint's copies of what it saves
PLUMBING = re.compile(r"/(while/cond/lt|while/body/add|dynamic_update_slice"
                      r"|dynamic_slice|broadcast_in_dim|closed_call"
                      r"|closed_call/add|remat\d*)$")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? "
                          r"([a-z][\w\-]*)\((.*)$")


def executed(hlo_text: str) -> list[tuple[str, str]]:
    """(name, opcode) of the instructions that run as ops: those of the
    entry computation and of the computations its loops, conditionals
    and calls run, not those inside fusions or reducers."""
    comps: dict[str, list] = {}
    entry = cur = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INSTRUCTION.match(line)):
            comps[cur].append(m.groups())
    seen, todo, out = {entry}, [entry], []
    while todo:
        for name, op, rest in comps[todo.pop()]:
            out.append((name, op))
            called = {"while": r"(?:condition|body)=%?([\w.\-]+)",
                      "conditional": r"%([\w.\-]+)",
                      "call": r"to_apply=%?([\w.\-]+)"}.get(op)
            for c in re.findall(called, rest) if called else ():
                if c in comps and c not in seen:
                    seen.add(c)
                    todo.append(c)
    return out


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("tiny"), {})
    # the MoE layer through its jnp path, as on a CPU by default: the
    # Pallas interpreter's loops carry no source metadata
    adapters = root / "benchmarks" / "chip" / "adapters"
    shutil.copy(adapters / "olmoe-1b-7b.py", adapters / "tiny-moe.py")
    return spec.Benchmark(root)


def compiled_step(bench) -> str:
    cfg, mix = bench.config("tiny-moe"), bench.traffic("smoke")
    program = model.Program(cfg, mix, bench.reference("tiny-moe"),
                            bench.adapter("tiny-moe"))
    state = jax.eval_shape(program.init_state, seed_key(5))
    batch = TrafficSource(mix, program.arch.vocab, 5).batch(0)
    return opscope.step_text(program.step, (*state, batch))


@pytest.fixture(scope="module")
def step_text(bench):
    return compiled_step(bench)


def test_costly_instructions_resolve_to_scopes(step_text):
    names = opscope.op_names(step_text)
    found: dict[str, set] = {}
    unscoped = []
    for name, op in executed(step_text):
        if op in FREE or name not in names:
            continue
        scope, phase = opscope.classify(names[name])
        if scope is None:
            unscoped.append(names[name])
        else:
            found.setdefault(scope, set()).add(phase)
    assert [n for n in unscoped if not PLUMBING.search(n)] == []
    moe = {f"{scopes.FFN}/{p}" for p in scopes.MOE_PARTS}
    assert set(found) <= set(scopes.TOP_LEVEL) | moe
    passes = {"forward", "backward", "recompute"}
    for scope in (scopes.ATTENTION, scopes.HEAD, f"{scopes.FFN}/router",
                  f"{scopes.FFN}/dispatch", f"{scopes.FFN}/experts"):
        assert found[scope] >= passes, scope
    assert found[scopes.EMBED] >= {"forward", "backward"}
    assert f"{scopes.FFN}/combine" in found
    assert found[scopes.OPTIMIZER] == found[scopes.GRAD_ACCUM] == {None}


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/while/body/closed_call/jvp()/while/body/closed_call/"
     "attention/mul", ("attention", "forward")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/ffn/router/top_k", ("ffn/router", "backward")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/ffn/dispatch/"
     "jit(take_along_axis)", ("ffn/dispatch", "recompute")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(head))/mul",
     ("head", "backward")),
    ("jit(train_step)/optimizer/mul", ("optimizer", None)),
    ("jit(train_step)/while/cond/lt", (None, None)),
])
def test_classify(op_name, want):
    assert opscope.classify(op_name) == want


def test_declared_children_are_kept(monkeypatch):
    """A child scope that the program declares under a top-level scope
    (``scopes.CHILDREN``) reads as ``<scope>/<child>``; the declaration
    takes the place of the MoE parts under ``ffn``."""
    assert opscope.CHILDREN == {scopes.FFN: scopes.MOE_PARTS}
    monkeypatch.setattr(scopes, "CHILDREN",
                        {scopes.ATTENTION: ("latent",)}, raising=False)
    try:
        importlib.reload(opscope)
        base = "jit(train_step)/while/body/closed_call/jvp()/"
        got = [opscope.classify(base + p) for p in (
            "attention/latent/dot_general", "attention/other/mul",
            "ffn/router/top_k")]
    finally:
        monkeypatch.undo()
        importlib.reload(opscope)
    assert got == [("attention/latent", "forward"), ("attention", "forward"),
                   ("ffn", "forward")]
    assert opscope.CHILDREN == {scopes.FFN: scopes.MOE_PARTS}


#: the scoped probe's classification as it stood before child scopes could
#: be declared: instructions by (scope, phase), and a digest of the whole
#: map
PROBE_SCOPES = {
    ("attention", "backward"): 325, ("attention", "forward"): 292,
    ("attention", "recompute"): 437, ("attention", None): 50,
    ("cast", "backward"): 10, ("cast", "forward"): 145,
    ("cast", "recompute"): 4, ("embed", "backward"): 11,
    ("embed", "forward"): 68, ("ffn", "backward"): 30,
    ("ffn", "forward"): 30, ("ffn", "recompute"): 46,
    ("ffn/combine", "backward"): 255, ("ffn/combine", "forward"): 13,
    ("ffn/combine", "recompute"): 6, ("ffn/dispatch", "backward"): 15,
    ("ffn/dispatch", "forward"): 172, ("ffn/dispatch", "recompute"): 136,
    ("ffn/dispatch", None): 15, ("ffn/experts", "backward"): 76,
    ("ffn/experts", "forward"): 39, ("ffn/experts", "recompute"): 79,
    ("ffn/router", "backward"): 137, ("ffn/router", "forward"): 256,
    ("ffn/router", "recompute"): 264, ("ffn/router", None): 4,
    ("grad_accum", None): 170, ("head", "backward"): 164,
    ("head", "forward"): 173, ("head", "recompute"): 60,
    ("optimizer", None): 1006, (None, "backward"): 76,
    (None, "forward"): 45, (None, None): 500}
PROBE_DIGEST = ("29b7cabeaf6cb10de8d1272ded3ae48650a633275dc967d0d64db183"
                "6c774fc3")


def test_scoped_probe_classifies_as_before(scoped_probe):
    m = scoped_probe.op_scopes
    assert Counter(m.values()) == PROBE_SCOPES
    items = sorted((k, str(v)) for k, v in m.items())
    assert hashlib.sha256(
        json.dumps(items).encode()).hexdigest() == PROBE_DIGEST


def test_scopes_leave_the_compiled_step_unchanged(bench, step_text,
                                                  monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_step(bench)
    assert all(opscope.classify(n)[0] is None
               for n in opscope.op_names(bare).values())
    assert (opscope.without_metadata(bare)
            == opscope.without_metadata(step_text))


def test_reader_compiles_the_harness_step(bench, step_text, monkeypatch,
                                          capsys):
    """The first reader of a traced run rebuilds the cell's step as the
    harness builds it, names the same instructions, keeps the map on the
    run and prints the top ops with their scope."""
    monkeypatch.setattr(spec, "Benchmark", lambda: bench)
    lines = [ln.strip() for ln in step_text.splitlines()
             if opscope.head(ln) is not None]
    ops = [trace.Op(ln, 10.0 * i, 10.0 * i + 5)
           for i, ln in enumerate(lines)]
    run = Run(cell=bench.cell(tiny.CELL), cfg=bench.config("tiny-moe"),
              mix=bench.traffic("smoke"), peaks={}, chips=1,
              flops_per_token=0.0, steps=1,
              trace=trace.Reduced(window=(0.0, 10.0 * len(ops)),
                                  ops={"/device:TPU:0": ops}))
    assert opscope.op_scopes(run) == opscope.scope_map(step_text)
    assert opscope.op_scopes(run) is run.op_scopes
    err = capsys.readouterr().err.splitlines()
    assert len(err) == 11 and all(" scope " in ln for ln in err[:10])
    assert err[10].startswith("unscoped_share ")
    assert err[10].endswith(" unjoined_share 0.000000")


HLO = """\
ENTRY %main (p: f32[4,4], q: bf16[4,4]) -> bf16[4,4] {
  %p = f32[4,4]{1,0} parameter(0), metadata={op_name="params['w']"}
  %q = bf16[4,4]{1,0} parameter(1), metadata={op_name="x"}
  %copy-start = (f32[4,4]{1,0:S(1)}, f32[4,4]{1,0}, u32[]{:S(2)}) copy-start(%p)
  %copy-done = f32[4,4]{1,0:S(1)} copy-done((f32[4,4]{1,0:S(1)}, f32[4,4]{1,0}, u32[]{:S(2)}) %copy-start)
  %copy = bf16[4,4]{0,1} copy(%copy-done), metadata={op_name="params['w']"}
  %dot = bf16[4,4]{1,0} dot(%copy, %q), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/jvp(attention)/dot_general"}
  %copy.1 = bf16[4,4]{0,1} copy(%dot)
  ROOT %add = bf16[4,4]{1,0} add(%copy.1, %q), metadata={op_name="jit(train_step)/while/cond/lt"}
}
"""


@pytest.mark.parametrize("name, want", [
    # the master weight's cast that XLA hoisted under the argument's name
    ("copy", ("cast", "forward")),
    # the prefetch of that weight serves the cast
    ("copy-start", ("cast", "forward")),
    ("copy-done", ("cast", "forward")),
    ("dot", ("attention", "forward")),
    # a layout copy takes the scope of what it reads
    ("copy.1", ("attention", "forward")),
    ("add", (None, None)),
])
def test_scope_map(name, want):
    heads = {opscope.head(ln).split(" = ")[0].lstrip("%"): opscope.head(ln)
             for ln in HLO.splitlines() if opscope.head(ln)}
    assert opscope.scope_map(HLO)[heads[name]] == want


def test_join_checks_type_and_opcode():
    """An op of the trace that another build numbered differently, so
    that the compiled text has its name for another instruction, counts
    under no scope."""
    copy = "%copy.1 = bf16[4,4]{0,1} copy(bf16[4,4]{1,0} %dot)"
    other = "%copy.1 = f32[4,4]{1,0} copy(f32[4,4]{1,0} %p)"
    reduced = trace.Reduced(window=(0.0, 10.0), ops={"/device:TPU:0": [
        trace.Op(copy, 0.0, 4.0), trace.Op(other, 4.0, 10.0)]})
    times = opscope.self_times(reduced, opscope.scope_map(HLO))
    assert times == {("attention", "forward"): pytest.approx(4e-9),
                     (None, None): pytest.approx(6e-9)}


MS = 1e6  # ns
#: a hand-made window of 2 steps on one chip: (name, start, end in ms,
#: scope, phase)
OPS = [("%a", 0, 10, "attention", "forward"),
       ("%b", 10, 30, "ffn/router", "backward"),
       ("%c", 30, 45, "ffn/experts", "recompute"),
       ("%d", 45, 50, "optimizer", None),
       ("%e", 50, 52, None, None),
       ("%f", 52, 60, "attention", "recompute")]


def op_text(name: str) -> str:
    return f"{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x), kind=kLoop"


def hand_made_run(traced: bool = True, named: bool = True) -> Run:
    reduced = trace.Reduced(
        window=(0.0, 100 * MS),
        ops={"/device:TPU:0": [trace.Op(op_text(n), a * MS, b * MS)
                               for n, a, b, _, _ in OPS]},
        spans=[("bench.window", 0, 100 * MS)])
    run = Run(cell={}, cfg={}, mix={}, peaks={}, chips=1,
              flops_per_token=0.0, steps=2,
              trace=reduced if traced else None)
    # what opscope.op_scopes would have compiled
    run.op_scopes = ({opscope.head(op_text(n)): (s, p)
                      for n, _, _, s, p in OPS} if named else None)
    return run


@pytest.mark.parametrize("metric, want", [
    ("attention_device_ms", (10 + 8) / 2),
    ("ffn_device_ms", (20 + 15) / 2),
    ("moe_routing_device_ms", 20 / 2),
    ("optimizer_device_ms", 5 / 2),
    ("recompute_device_ms", (15 + 8) / 2),
])
def test_readers(metric, want):
    read = spec.Benchmark().reader(metric)
    assert read(hand_made_run()) == pytest.approx(want)
    assert read(hand_made_run(traced=False)) is None
    # a program that names none of its work
    assert read(hand_made_run(named=False)) is None


@pytest.fixture(scope="module")
def scoped_probe():
    step = gzip.decompress(
        (TESTDATA / "trace_probe_scoped.hlo.txt.gz").read_bytes()).decode()
    run = Run(cell={}, cfg={}, mix={}, peaks={}, chips=1,
              flops_per_token=0.0, steps=3,
              trace=trace.reduce(trace.load(
                  (TESTDATA / "trace_probe_scoped.xplane.pb.gz")
                  .read_bytes())))
    run.op_scopes = opscope.scope_map(step)
    return run


def test_scoped_probe_resolves_busy_time(scoped_probe):
    """At least 98% of device time falls under a named scope and every
    op of the trace joins an instruction of the executable that ran.
    What stays unscoped is the microbatch loop's own time between its
    body's ops and the zeros the backward layer scan starts from, and
    under 0.1% of plumbing: the batch's reshape, copies of the inputs
    and constants, buffer allocation."""
    r = scoped_probe.trace
    times = opscope.self_times(r, scoped_probe.op_scopes)
    busy = r.busy_s()
    scoped = sum(t for (scope, _), t in times.items() if scope is not None)
    assert sum(times.values()) == pytest.approx(busy)
    assert scoped >= 0.98 * busy
    ops = r.ops["/device:TPU:0"]
    assert all(opscope.head(o.name) in scoped_probe.op_scopes for o in ops)
    plumbing = sum(
        t for o, t in trace._self_times(ops, *r.window)
        if scoped_probe.op_scopes[opscope.head(o.name)][0] is None
        and not o.short.startswith(("while", "broadcast")))
    assert plumbing * 1e-9 < 0.001 * busy


@pytest.mark.parametrize("metric", NAMED_METRICS)
def test_scoped_probe_metrics(scoped_probe, metric):
    value = spec.Benchmark().reader(metric)(scoped_probe)
    assert 0 < value < scoped_probe.trace.window_s * 1e3
