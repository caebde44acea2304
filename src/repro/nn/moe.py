"""Feed-forward layers: dense SwiGLU and top-k MoE with capacity dispatch.

The MoE uses scatter/gather dispatch with a per-expert capacity (GShard
style, capacity factor 1.25 by default): static shapes (shardable under
pjit — experts lay on the ``model`` mesh axis), FLOPs proportional to the
*active* experts, tokens over capacity dropped through the residual path.
Router load-balance auxiliary loss follows Switch Transformer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.obs import scopes


def init_dense_ffn(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    return {
        "w1": jax.random.normal(k1, (d_model, d_ff)) * s_in,
        "w3": jax.random.normal(k3, (d_model, d_ff)) * s_in,
        "w2": jax.random.normal(k2, (d_ff, d_model)) * s_out,
    }


def dense_ffn(p, x):
    from repro.parallel.act import shard_last_dim

    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    return shard_last_dim(h) @ p["w2"]


def init_moe(key, d_model: int, d_ff: int, num_experts: int, *,
             router_scale: float | None = None):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    return {
        "router": jax.random.normal(kr, (d_model, num_experts))
        * (router_scale or s_in),
        "w1": jax.random.normal(k1, (num_experts, d_model, d_ff)) * s_in,
        "w3": jax.random.normal(k3, (num_experts, d_model, d_ff)) * s_in,
        "w2": jax.random.normal(k2, (num_experts, d_ff, d_model)) * s_out,
    }


def moe_capacity(num_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float = 1.25) -> int:
    cap = int(math.ceil(num_tokens * top_k * capacity_factor / num_experts))
    return max(8, -(-cap // 8) * 8)  # round up to 8 for TPU lane alignment


def moe_route(router, x, *, top_k: int, capacity: int):
    """Top-k capacity routing for ``x (G, S, D)``.

    Returns ``(probs, gate, eid_f, pos, keep)`` where probs: (G, S, E)
    router softmax; gate: (G, S, K) renormalized top-k weights; and
    eid_f / pos / keep: (G, S·K) flat per-(token, k) expert id,
    position-in-expert (exclusive running count within the group) and
    under-capacity mask.  Shared by the reference oracle, the jnp slot
    path and the Pallas kernels — the property tests pin its invariants.
    """
    G, S, _ = x.shape
    E = router.shape[1]
    K = top_k

    logits = (x @ router).astype(jnp.float32)                # (G, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, K)                      # (G, S, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) slot within its expert, per group
    eid_f = eid.reshape(G, S * K)                            # (G, NK)
    onehot = jax.nn.one_hot(eid_f, E, dtype=jnp.int32)       # (G, NK, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1                # exclusive rank
    pos = jnp.take_along_axis(pos_in_e, eid_f[..., None], axis=2)[..., 0]
    keep = pos < capacity                                    # (G, NK)
    return probs, gate, eid_f, pos, keep


def ref_dispatch(x, eid_f, safe_pos, keep, *, num_experts: int,
                 capacity: int, top_k: int):
    """Oracle scatter dispatch: K-repeated source + ``.at[].add`` into
    the (E, C) capacity slabs.  Single source of truth for the reference
    path — ``moe_ffn(impl="ref")`` and ``bench_kernels`` both use it."""
    E, C, K = num_experts, capacity, top_k
    D = x.shape[-1]

    def dispatch(xg, eg, pg, kg):
        src = jnp.repeat(xg, K, axis=0) * kg[:, None].astype(xg.dtype)
        return jnp.zeros((E, C, D), xg.dtype).at[eg, pg].add(src,
                                                             mode="drop")

    return jax.vmap(dispatch)(x, eid_f, safe_pos, keep)


def ref_combine(buf, eid_f, safe_pos, w, *, top_k: int):
    """Oracle gather combine: explicit (G, N·K, D) gather + gate-weighted
    sum over k.  ``w (G, N·K)`` is the gate·keep weight."""
    G, NK = eid_f.shape
    S, K = NK // top_k, top_k
    D = buf.shape[-1]

    def combine(og, eg, pg):
        return og[eg, pg]                                    # (NK, D)

    y_f = jax.vmap(combine)(buf, eid_f, safe_pos)            # (G, NK, D)
    return (y_f * w[..., None].astype(y_f.dtype)).reshape(G, S, K, D).sum(2)


def moe_ffn(p, x, *, top_k: int, capacity_factor: float = 1.25,
            impl: str = "auto"):
    """x: (B, S, D) → (y (B, S, D), aux) with aux = load-balance loss terms.

    GShard-style *grouped* dispatch: each sequence is a routing group
    (G = B groups, shardable over the data axes), with per-group expert
    capacity C = ceil(S·K·cf / E).  Position-in-expert is a cumulative
    count *within the group* — no cross-shard prefix sum — so every step
    of dispatch is data-parallel while the expert dim lays on the
    ``model`` axis.  Tokens over a group's capacity fall through the
    residual path.

    ``impl="ref"`` is the pure-JAX scatter/gather oracle (K-repeated
    source, ``.at[].add`` dispatch, explicit gather combine).  Any other
    impl routes the data movement through the fused dispatch/combine
    layer in :mod:`repro.kernels.ops` (``auto`` → compiled Pallas on TPU,
    jnp slot formulation elsewhere; ``interpret``/``slot``/``pallas``
    force a path), with gradients via the kernels' ``custom_vjp``.
    """
    from repro.parallel.act import shard_batch_act, shard_moe_group_buffer

    B, S, D = x.shape
    E = p["router"].shape[1]
    K = top_k
    C = moe_capacity(S, E, K, capacity_factor)               # per group

    with jax.named_scope(scopes.ROUTER):
        probs, gate, eid_f, pos, keep = moe_route(p["router"], x, top_k=K,
                                                  capacity=C)
        safe_pos = jnp.where(keep, pos, 0)

    with jax.named_scope(scopes.DISPATCH):
        if impl == "ref":
            buf = ref_dispatch(x, eid_f, safe_pos, keep, num_experts=E,
                               capacity=C, top_k=K)          # (G, E, C, D)
        else:
            from repro.kernels import ops as kops

            buf = kops.moe_dispatch(x, eid_f, pos,
                                    keep.astype(jnp.float32),
                                    num_experts=E, capacity=C, top_k=K,
                                    impl=impl)
        buf = shard_moe_group_buffer(buf)

    # batched expert SwiGLU — the expert dim shards over the model axis
    with jax.named_scope(scopes.EXPERTS):
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w1"]))
        h = h * jnp.einsum("gecd,edf->gecf", buf, p["w3"])
        out = jnp.einsum("gecf,efd->gecd", h, p["w2"])       # (G, E, C, D)
        out = shard_moe_group_buffer(out)

    with jax.named_scope(scopes.COMBINE):
        w = (gate.reshape(B, S * K) * keep).astype(x.dtype)
        if impl == "ref":
            y = ref_combine(out, eid_f, safe_pos, w, top_k=K)
        else:
            y = kops.moe_combine(
                out, eid_f.reshape(B, S, K), safe_pos.reshape(B, S, K),
                w.reshape(B, S, K), impl=impl,
            )
        y = shard_batch_act(y)

    # Switch-style load-balance aux loss
    with jax.named_scope(scopes.ROUTER):
        eid = eid_f.reshape(B, S, K)
        density = jax.nn.one_hot(eid[..., 0], E).mean((0, 1))  # top-1 share
        mean_prob = probs.mean((0, 1))
        aux = E * jnp.sum(density * mean_prob)
    return y, {"aux_loss": aux, "dropped": 1.0 - keep.mean()}
