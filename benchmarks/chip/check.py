"""The comparison that decides ``correct``.

Three numbers, each a gap between the program's reading and the float32
reference's over the first three training steps, which the timed path
itself ran:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: the worst leaf's gap between the norms of the first
  step's gradient as AdamW took it, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- ``delta_gap``: the same for the norm of each leaf's change after the
  three steps, over the leaves that the reference's first gradient moves
  (at least a thousandth of the median leaf's norm).

Each number has a limit of its own in ``limits/<cell>.json``, set from the
program's readings over a dozen seeds and from the lower-precision
control's; the file records both.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "delta_gap")
#: leaves whose reference gradient is under this share of the median
#: leaf's move under AdamW by round-off alone
MOVED_SHARE = 1e-3


def _worst_leaf(prog: dict, ref: dict, leaves) -> float:
    floor = statistics.median(ref[k] for k in leaves)
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves)


def gaps(prog: dict, ref: dict) -> dict:
    """Readings ``{"loss": [...], "grad": {leaf: norm}, "delta": {...}}``
    of the program and the reference → the compared numbers."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("the program's and the reference's leaves differ")
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"],
                                                   strict=True))
    g_floor = statistics.median(ref["grad"].values())
    moved = [k for k, g in ref["grad"].items() if g >= MOVED_SHARE * g_floor]
    return {
        "loss_gap": loss,
        "grad_gap": _worst_leaf(prog["grad"], ref["grad"], ref["grad"]),
        "delta_gap": _worst_leaf(prog["delta"], ref["delta"], moved),
    }


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]["limit"]
               for k in NUMBERS)
