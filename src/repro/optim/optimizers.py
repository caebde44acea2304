"""AdamW / SGD + gradient clipping — tree-based, shardable.

Optimizer state mirrors the parameter pytree leaf-for-leaf, so whatever
sharding the parameters carry propagates to the moments (ZeRO-style
sharding of optimizer state falls out of the param sharding rules).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs import scopes


@dataclasses.dataclass
class OptState:
    mu: Any
    nu: Any
    count: jax.Array

jax.tree_util.register_pytree_node(
    OptState,
    lambda s: ((s.mu, s.nu, s.count), None),
    lambda aux, children: OptState(*children),
)


def adamw_init(params) -> OptState:
    z = jax.tree.map(jnp.zeros_like, params)
    return OptState(mu=z, nu=jax.tree.map(jnp.zeros_like, params),
                    count=jnp.zeros((), jnp.int32))


def clip_by_global_norm(grads, max_norm: float):
    with jax.named_scope(scopes.OPTIMIZER):
        g2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        norm = jnp.sqrt(g2)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
        return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), norm


def adamw_update(params, grads, state: OptState, *, lr: float,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.0):
    with jax.named_scope(scopes.OPTIMIZER):
        count = state.count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu,
                          grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                          state.nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def upd(p, m, v):
            step = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if weight_decay:
                step = step + weight_decay * p
            return (p - lr * step).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, OptState(mu=mu, nu=nu, count=count)


def sgd_update(params, grads, *, lr: float, ):
    return jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype), params, grads)
