"""Operations and bytes that the work requires, from shapes alone.

``train_flops_per_token`` counts the forward and backward passes that a
training step must do per token: backward = 2 × forward, recomputation
not counted.  The forward count is each configuration's own
(``reference/<config>.py``): matmuls only, 2 operations per multiply-add,
causal attention over the keys at or before each query, a mixture of
experts over its top-k experts with no capacity padding, the LM head over
the held vocabulary and not the program's pad rows.

``kernel_bytes`` gives the least HBM traffic of one MoE dispatch or
combine kernel call from the operand and result shapes that the device
trace records for it.
"""

from __future__ import annotations

import math
import re


def train_flops_per_token(reference, n: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a token requires, from the forward count
    of the configuration's reference (``forward_flops_per_token``)."""
    return 3.0 * reference.forward_flops_per_token(n, seq_len)


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_ITEMSIZE) + r")\[([\d,]*)\]")
#: "<result type> <opcode>(<operands>), <attributes>"
_CALL = re.compile(r"(.*?)\s[a-z][\w\-.]*\((.*)", re.S)


def hlo_shapes(op_text: str):
    """``(result, operands)`` of one HLO instruction's text, each shape a
    ``(dtype, dims)`` pair; a tuple result counts as its parts."""
    rhs = op_text.partition(" = ")[2]
    m = _CALL.match(rhs)
    if m is None:
        raise ValueError(f"not an HLO instruction: {op_text[:80]!r}")
    head, args = m.group(1), m.group(2).split("), ", 1)[0]

    def shapes(text):
        return [(t, tuple(int(x) for x in dims.split(",") if x))
                for t, dims in _SHAPE.findall(text)]

    return shapes(head), shapes(args)


def nbytes(shape) -> int:
    dtype, dims = shape
    return _ITEMSIZE[dtype] * math.prod(dims)


def kernel_bytes(kind: str, op_text: str) -> int:
    """Least bytes moved by one call of the MoE ``dispatch`` or
    ``combine`` kernel.

    dispatch(slot_src, x, slot_w) → slabs: every operand read once and the
    slabs written once.  combine(eid, pos, buf, w) → tokens: the index
    and weight operands read once, one buffer row read per (token, k)
    pair, and the tokens written once (the kernel never needs the slab
    rows that no pair names).
    """
    result, ops = hlo_shapes(op_text)
    out = sum(nbytes(s) for s in result)
    if kind == "dispatch":
        return out + sum(nbytes(s) for s in ops)
    if kind == "combine":
        eid, pos, buf, w = ops
        row = _ITEMSIZE[buf[0]] * math.prod(buf[1][3:])
        pairs = math.prod(eid[1])
        return out + nbytes(eid) + nbytes(pos) + nbytes(w) + pairs * row
    raise ValueError(f"unknown kernel kind {kind!r}")


def kernel_flops(kind: str, op_text: str) -> int:
    """Arithmetic of one kernel call: dispatch scales each slab element
    once; combine multiplies and adds K rows into each output element."""
    result, ops = hlo_shapes(op_text)
    out = sum(math.prod(dims) for _, dims in result)
    if kind == "dispatch":
        return out
    k = ops[3][1][1]                        # w: (G, K, S, 1, 1)
    return 2 * k * out


def roofline_share(kind: str, ops, peaks: dict) -> tuple[float, str] | None:
    """Share (%) of the least time over the measured time of a kernel's
    calls, and which bound governs the least time; None without calls."""
    if not ops:
        return None
    least = {"memory": 0.0, "compute": 0.0}
    for o in ops:
        t_mem = kernel_bytes(kind, o.name) / peaks["hbm_bytes_per_s"]
        t_ops = kernel_flops(kind, o.name) / peaks["bf16_flops"]
        least["memory" if t_mem >= t_ops else "compute"] += max(t_mem, t_ops)
    spent = sum(o.end - o.start for o in ops) * 1e-9
    bound = max(least, key=least.get)
    return 100.0 * sum(least.values()) / spent, bound
