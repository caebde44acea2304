"""Pure-jnp oracles for the Pallas kernels (the allclose references)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        softcap: float | None = None):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd), each KV head serving
    H // KV query heads → (B, H, Sq, hd).  Direct softmax attention."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= qpos >= kpos
    if window is not None:
        ok &= (qpos - kpos) < window
    s = jnp.where(ok, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)


def embedding_bag_ref(ids, table):
    """ids: (N, bag) int32; table: (V, dim) → (N, dim) sum-pooled."""
    return table[ids].sum(axis=1)
