"""Names the program gives its work, for readers of a profile.

Device scopes are ``jax.named_scope`` names in the train step.  JAX
writes them into each HLO instruction's ``op_name`` metadata, which
survives fusion, ``jax.checkpoint`` and ``scan``; they are metadata only
and leave the compiled program as it is.  Every op of the step falls
under one top-level scope:

* :data:`EMBED` — the token gather and its scale;
* :data:`ATTENTION` — ``norm1`` through the mixer's residual add, for
  every mixer kind;
* :data:`FFN` — ``norm2`` through the FFN's residual add; a MoE layer
  splits it into :data:`MOE_PARTS` (router, dispatch, experts, combine),
  each with the relayouts beside its kernel;
* :data:`HEAD` — the final norm and the chunked loss;
* :data:`CAST` — the per-layer cast of the f32 master weights to the
  compute dtype;
* :data:`OPTIMIZER` — gradient clipping and the AdamW update;
* :data:`GRAD_ACCUM` — the microbatch scan's accumulation.

An op's path says which pass it belongs to: a forward op sits under
``jvp(<scope>)``, its backward under ``transpose(jvp(<scope>))``, and
the backward pass's recomputation of a checkpointed layer under
``.../checkpoint/rematted_computation/<scope>/...``.

Host spans (:func:`repro.obs.trace.span`) land in the obs ring when it
is on and in the profiler's trace whenever a profiler session is
active, on the device ops' clock.

Stdlib only, like the rest of the package's import path: the benchmark's
trace readers import these names, so a renamed scope fails a test
instead of reading 0.
"""

from __future__ import annotations

EMBED = "embed"
ATTENTION = "attention"
FFN = "ffn"
HEAD = "head"
CAST = "cast"
OPTIMIZER = "optimizer"
GRAD_ACCUM = "grad_accum"
#: every op of the train step falls under exactly one of these
TOP_LEVEL = (EMBED, ATTENTION, FFN, HEAD, CAST, OPTIMIZER, GRAD_ACCUM)

ROUTER = "router"
DISPATCH = "dispatch"
EXPERTS = "experts"
COMBINE = "combine"
#: children of :data:`FFN` in a MoE layer
MOE_PARTS = (ROUTER, DISPATCH, EXPERTS, COMBINE)

#: path component JAX gives the backward pass's recomputation of a
#: ``jax.checkpoint``-ed function
REMAT = "rematted_computation"

#: ``PrefetchLoader.__next__`` blocked on its queue
DATA_WAIT = "data.wait"
#: the loader's worker making a batch (``next(dataset)``)
DATA_PRODUCE = "data.produce"
#: the loader's worker blocked on a full queue
DATA_PUT_WAIT = "data.put_wait"
#: one pass of Python's cyclic garbage collector; arg ``generation``
PYTHON_GC = "python.gc"
