"""Device ops by the program's named scopes.

The trace's ``XLA Ops`` events name each op by its HLO instruction and
nothing more.  The program names its work with ``jax.named_scope``
(``repro.obs.scopes``), which JAX writes into each instruction's
``op_name`` metadata.  So the first reader of a traced run that asks
(after the window and the reference, so that neither set-up nor the
window's compile count moves) builds the cell's program again, compiles
its step for the window's argument shapes and reads every instruction's
``op_name`` from the compiled text.  The join to the trace is on the
instruction's name, result type and opcode (``head``), which both print
alike: two builds of one step can number a few instructions apart (the
async copies that prefetch weights, some reshapes), and an op whose
head the compiled text does not have counts under no scope rather than
under another instruction's.  A fusion carries the ``op_name`` XLA gave
it, normally its root's; an instruction XLA made without one takes its
neighbours' (``scope_map``).  The reader prints the run's top ops with
their scope and phase on standard error, and the shares of device time
that fall under no scope and that found no instruction to join.

The step is built again here as the harness builds it (``_step``), since
the harness keeps no handle on the step it timed; a change to how the
harness builds its step shows as time that finds no instruction.

A scope is the first top-level scope name on the ``op_name`` path, and
the child scope right beneath it where the program declares that child
for that parent (``CHILDREN``: ``scopes.CHILDREN``, or else the MoE
parts under ``ffn``, as ``ffn/router``); the phase is
``recompute`` where the path holds the checkpoint's
``rematted_computation``, ``backward`` where it holds a ``transpose(``,
``forward`` where it holds a ``jvp(``, and None outside differentiation
(the optimizer, the accumulation).  A program without named scopes has
no ``repro.obs.scopes``: then nothing is compiled and every reading here
is None.
"""

from __future__ import annotations

import functools
import re
import sys
from collections import defaultdict

from benchmarks.chip import trace

try:
    from repro.obs import scopes
except ImportError:  # a program that names none of its work
    scopes = None

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)
#: one instruction: its name and the rest of its line
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OPCODE = re.compile(r" ?([a-z][\w\-]*)\(")
_ELEMENT = re.compile(r"^([a-z]\w*)\[")
#: floating element types, widest first
_FLOATS = ("f64", "f32", "bf16", "f16")
_TABLES = re.compile(r"\n\nFileNames\n.*?\n\n\n(?=%|ENTRY)", re.S)
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_REF = re.compile(r"%([\w.\-]+)")
#: a path component's innermost name: ``transpose(jvp(head))`` → ``head``
_INNER = re.compile(r"^(?:[\w.\-]*\()*([\w.\-]*)\)*$")


def _inner(part: str) -> str:
    m = _INNER.match(part)
    return m.group(1) if m else part


def without_metadata(hlo_text: str) -> str:
    """A compiled module's text without what names and places its
    instructions in the source: the tables of files and stack frames
    before the first computation, and each instruction's
    ``metadata={...}``."""
    text = _TABLES.sub("\n\n", hlo_text)
    return _METADATA.sub("", text)


def _closing(text: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _parse(rest: str):
    """(result type, opcode, operand names) of the text after an
    instruction's ``=``; None where it names no opcode."""
    end = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    m = _OPCODE.match(rest, max(end, 0))
    if m is None:
        return None
    args = rest[m.end() - 1:_closing(rest, m.end() - 1)]
    return rest[:end], m.group(1), _REF.findall(args)


@functools.cache
def head(text: str) -> str | None:
    """``name = result-type opcode`` of one instruction's text, as a
    trace op and the compiled text both give it; None for other text.
    Cached: a trace repeats each op's text at every call."""
    m = _LINE.match(text)
    parsed = m and _parse(m.group(2))
    if not parsed:
        return None
    return f"{m.group(1)} = {parsed[0]} {parsed[1]}"


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name → its ``op_name``, for every instruction of a
    compiled module's text that has one."""
    return dict(_INSTR.findall(hlo_text))


#: top-level scope → the child scopes kept beneath it: the program's
#: ``scopes.CHILDREN``, or where it has none, the MoE parts under ``ffn``
CHILDREN = {} if scopes is None else getattr(
    scopes, "CHILDREN", {scopes.FFN: scopes.MOE_PARTS})


def classify(op_name: str) -> tuple[str | None, str | None]:
    """(scope, phase) of one ``op_name``; scope None outside every named
    scope."""
    parts = [_inner(p) for p in op_name.split("/")]
    scope = None
    for i, name in enumerate(parts):
        if name in scopes.TOP_LEVEL:
            scope = name
            if (i + 1 < len(parts)
                    and parts[i + 1] in CHILDREN.get(name, ())):
                scope = f"{name}/{parts[i + 1]}"
            break
    if scopes.REMAT in parts:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = None
    return scope, phase


def scope_map(hlo_text: str) -> dict[str, tuple[str | None, str | None]]:
    """``head`` of each instruction → (scope, phase), for a compiled
    module's text.

    An instruction XLA made itself has no ``op_name`` of the step's own,
    only none or an argument's name.  If it narrows a float operand
    (f32 to bf16), it is the master weights' cast, hoisted out of the
    loops: ``cast``, forward.  Any other (a layout copy, an async copy's
    halves, a reshape beside a kernel, the pieces of a rewritten
    cumulative sum) takes the scope and phase of the nearest instruction
    it reads from through other such instructions, or else of the
    nearest one that reads it."""
    names = op_names(hlo_text)
    instrs = {}
    for name, rest in _LINE.findall(hlo_text):
        parsed = _parse(rest)
        if parsed:
            instrs[name] = parsed
    users: dict[str, list[str]] = defaultdict(list)
    for name, (_, _, args) in instrs.items():
        for a in args:
            users[a].append(name)
    known = {}
    for name, (typ, _, args) in instrs.items():
        if names.get(name, "").startswith("jit("):
            known[name] = classify(names[name])
        elif _narrows(typ, [instrs[a][0] for a in args if a in instrs]):
            known[name] = (scopes.CAST, "forward")
    out = dict(known)
    operands = {name: args for name, (_, _, args) in instrs.items()}
    for name in instrs.keys() - known.keys():
        out[name] = (_nearest(name, operands, known)
                     or _nearest(name, users, known) or (None, None))
    return {f"{name} = {instrs[name][0]} {instrs[name][1]}": scope
            for name, scope in out.items()}


def _element(typ: str) -> str | None:
    m = _ELEMENT.match(typ)
    return m.group(1) if m else None


def _narrows(typ: str, operand_types: list[str]) -> bool:
    """Whether an instruction of result type ``typ`` narrows its one
    float operand to a narrower float."""
    out = _element(typ)
    ins = {_element(t) for t in operand_types}
    if out not in _FLOATS or len(ins) != 1:
        return False
    (src,) = ins
    return src in _FLOATS and _FLOATS.index(src) < _FLOATS.index(out)


def _nearest(start: str, edges: dict, known: dict):
    """(scope, phase) of the nearest instruction with a scope met going
    from ``start`` along ``edges`` through instructions not in
    ``known``; one in ``known`` outside every scope ends its path.  None
    where no path meets a scope."""
    seen, frontier = {start}, [start]
    while frontier:
        step = []
        for n in frontier:
            for m in edges.get(n, ()):
                if m in seen:
                    continue
                seen.add(m)
                if m in known:
                    if known[m][0] is not None:
                        return known[m]
                else:
                    step.append(m)
        frontier = step
    return None


def step_text(step, shapes) -> str:
    """Compiled text of a jitted step for its arguments' ``shapes``, with
    the compile cache keyed on the metadata too, so that an executable
    cached from a build without this program's scopes is not taken for
    it."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return step.lower(*shapes).compile().as_text()
    finally:
        jax.config.update(flag, was)


def op_scopes(run) -> dict | None:
    """(scope, phase) of each instruction of the traced run's compiled
    step by ``head``, kept on the run for the next reader; None for a
    program without scopes."""
    if "op_scopes" not in vars(run):
        run.op_scopes = None
        if scopes is not None:
            run.op_scopes = scope_map(step_text(*_step(run)))
            report(run.trace, run.op_scopes)
    return run.op_scopes


def report(reduced: trace.Reduced, op_scopes: dict) -> None:
    """On standard error: the window's top 10 ops by self time with
    their scope and phase, then the shares of device time under no scope
    and without an instruction to join."""
    lo, hi = reduced.window
    total: dict[str, float] = defaultdict(float)
    for ops in reduced.ops.values():
        for o, t in trace._self_times(ops, lo, hi):
            total[o.name] += t * 1e-9
    busy = sum(total.values()) or 1.0
    for text, s in sorted(total.items(), key=lambda kv: -kv[1])[:10]:
        scope, phase = op_scopes.get(head(text), (None, None))
        print(f"op {trace.Op(text, 0, 0).short} {s:.6f} s scope {scope} "
              f"phase {phase}", file=sys.stderr)
    unscoped = sum(s for text, s in total.items()
                   if op_scopes.get(head(text), (None,))[0] is None)
    unjoined = sum(s for text, s in total.items()
                   if head(text) not in op_scopes)
    print(f"unscoped_share {unscoped / busy:.6f} "
          f"unjoined_share {unjoined / busy:.6f}", file=sys.stderr)


def _step(run):
    """The cell's jitted step, built as the harness builds it, and the
    shapes of the window's arguments."""
    import jax

    from benchmarks.chip import model, spec
    from benchmarks.chip.reference.common import seed_key
    from benchmarks.chip.traffic import TrafficSource

    bench = spec.Benchmark()
    config = run.cell["config"]
    program = model.Program(run.cfg, run.mix, bench.reference(config),
                            bench.adapter(config))
    state = jax.eval_shape(program.init_state, seed_key(0))
    batch = TrafficSource(run.mix, program.arch.vocab, 0).batch(0)
    return program.step, (*state, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))


def self_times(reduced: trace.Reduced,
               op_scopes: dict) -> dict[tuple, float]:
    """Device self time (s) of the window's ops by (scope, phase),
    averaged over chips; an op whose ``head`` the compiled text does not
    have counts under (None, None)."""
    lo, hi = reduced.window
    out: dict[tuple, float] = defaultdict(float)
    for ops in reduced.ops.values():
        for o, t in trace._self_times(ops, lo, hi):
            out[op_scopes.get(head(o.name), (None, None))] += t * 1e-9
    n = len(reduced.ops) or 1
    return {k: v / n for k, v in out.items()}


def device_ms(run, *, under=(), phase: str | None = None) -> float | None:
    """Device self time a step (ms) of the window's ops whose scope is
    one of ``under`` or lies beneath one, and, given ``phase``, in that
    phase.  None without a trace or the program's scopes."""
    if run.trace is None or not run.trace.ops or not run.steps:
        return None
    names = op_scopes(run)
    if not names:
        return None
    total = 0.0
    for (scope, ph), s in self_times(run.trace, names).items():
        if under and not (scope is not None and any(
                scope == u or scope.startswith(u + "/") for u in under)):
            continue
        if phase is not None and ph != phase:
            continue
        total += s
    return 1e3 * total / run.steps
