"""HeterPS stage pipeline — GPipe-style schedule on a ``stage`` mesh axis.

The paper (§3, §5.1) partitions the model into stages (consecutive layers
on one resource type, from the scheduling plan), runs data parallelism
*within* a stage and pipeline parallelism *between* stages, with
microbatches flowing stage-to-stage.  TPU mapping (DESIGN.md §2): stages
live on submeshes of the pod — here a dedicated ``stage`` mesh axis —
and activations move with ``jax.lax.ppermute`` (ICI neighbor hops).

The schedule is the classic fill/drain loop: ``T = M + S - 1`` ticks for
``M`` microbatches over ``S`` stages; at tick ``t`` stage ``s`` computes
microbatch ``t - s``.  The loop is differentiable (ppermute transposes to
the reverse permutation), so ``jax.grad`` of the pipelined loss yields
the backward pipeline automatically — 1F1B-style scheduling is left to
XLA's latency-hiding scheduler on real hardware.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def make_stage_mesh(num_stages: int):
    return jax.make_mesh((num_stages,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches,
    mesh,
    *,
    axis: str = "stage",
):
    """Run ``microbatches`` through the stage pipeline.

    stage_fn: (params_one_stage, x (mb, d)) → y (mb, d) — the same
      callable for every stage (heterogeneity lives in the params).
    stage_params: pytree with leading dim = num_stages (one slice per
      stage, produced from the scheduling plan's stage partition).
    microbatches: (M, mb, d) — M microbatches.
    Returns (M, mb, d_out): the last stage's outputs, microbatch order.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    T = M + S - 1
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def body(params_blk, xs):
        params_local = jax.tree.map(lambda a: a[0], params_blk)
        sidx = jax.lax.axis_index(axis)
        state = jnp.zeros_like(
            jax.eval_shape(lambda p, x: stage_fn(p, x), params_local, xs[0])
        )
        outs = []
        for t in range(T):
            mb_idx = min(t, M - 1)
            inp = jnp.where(sidx == 0, xs[mb_idx], state)
            y = stage_fn(params_local, inp)
            outs.append(y)
            if t < T - 1:
                state = jax.lax.ppermute(y, axis, fwd_perm)
        # microbatch m exits the last stage at tick m + S - 1
        stacked = jnp.stack(outs[S - 1 :], axis=0)  # (M, mb, d)
        return stacked[None]  # (1, M, mb, d) per-stage block

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(axis),
    )(stage_params, microbatches)
    return out[-1]  # the last stage's collected outputs


def pipeline_loss(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    microbatches,
    labels,
    mesh,
    *,
    axis: str = "stage",
):
    """Differentiable pipelined loss: mean over microbatches of
    ``loss_fn(last_stage_out, labels_mb)``.  ``jax.grad`` of this w.r.t.
    ``stage_params`` backpropagates through the ppermute chain — the
    backward pipeline."""
    outs = pipeline_apply(stage_fn, stage_params, microbatches, mesh, axis=axis)
    losses = jax.vmap(loss_fn)(outs, labels)
    return losses.mean()


def stack_stage_params(per_stage: list):
    """[stage pytrees with identical structure] → stacked (S, …) pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)
