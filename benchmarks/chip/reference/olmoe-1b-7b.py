"""Plain float32 reference of OLMoE-1B-7B (arXiv:2409.02060), as the
configuration file runs it.

Per layer: RMSNorm, multi-head attention with RMSNorm on q and k and
rotary positions, residual; RMSNorm, top-k mixture of SwiGLU experts,
residual.  Then a final RMSNorm, the LM head over the vocabulary slice,
the mean token cross-entropy and the router's load-balance loss.

The file's ``departures`` are followed where the program departs from the
published model: top-k weights renormalized, per-sequence expert capacity
with drops, a load share counted over each token's first expert, qk-norm
per head, eps 1e-6, interleaved rotary pairs, pad rows in the vocabulary.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common as c


def dims(cfg: dict) -> dict:
    dep, hp = cfg["departures"], cfg["assumed"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": hp["head_dim"]["value"],
        "ffn": cfg["intermediate_size"], "experts": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "vocab_rows": dep["vocab_rows"]["as_run"],
        "eps": dep["rms_norm_eps"]["as_run"],
        "theta": cfg["rope_theta"],
        "renorm_topk": dep["norm_topk_prob"]["as_run"],
        "capacity_factor": dep["moe_capacity_factor"]["as_run"],
        "aux_coef": cfg["router_aux_loss_coef"],
    }


def forward_flops_per_token(n: dict, seq_len: int) -> float:
    """Forward FLOPs a token requires: attention, the router and its top-k
    experts (no capacity padding) in each layer, then the head."""
    moe = 2.0 * n["d"] * n["experts"] + n["top_k"] * c.swiglu_flops(
        n["d"], n["ffn"])
    return (n["layers"] * (c.attention_flops(n, seq_len) + moe)
            + c.head_flops(n))


def init_params(key, cfg: dict) -> dict:
    n = dims(cfg)
    d, hd, H, KV, F, E = (n["d"], n["head_dim"], n["heads"], n["kv_heads"],
                          n["ffn"], n["experts"])
    top = c.normal_init(key, {
        "embed": ((n["vocab_rows"], d), 1 / math.sqrt(d)),
        "lm_head": ((d, n["vocab_rows"]), 1 / math.sqrt(d)),
    })
    top["final_norm"] = jnp.ones((d,), c.F32)
    layers = []
    for i in range(n["layers"]):
        lp = c.normal_init(jax.random.fold_in(key, 1000 + i), {
            "wq": ((d, H * hd), 1 / math.sqrt(d)),
            "wk": ((d, KV * hd), 1 / math.sqrt(d)),
            "wv": ((d, KV * hd), 1 / math.sqrt(d)),
            "wo": ((H * hd, d), 1 / math.sqrt(H * hd)),
            "router": ((d, E), 1 / math.sqrt(d)),
            "w1": ((E, d, F), 1 / math.sqrt(d)),
            "w3": ((E, d, F), 1 / math.sqrt(d)),
            "w2": ((E, F, d), 1 / math.sqrt(F)),
        })
        for norm, width in (("attn_norm", d), ("ffn_norm", d),
                            ("q_norm", hd), ("k_norm", hd)):
            lp[norm] = jnp.ones((width,), c.F32)
        layers.append(lp)
    top["layers"] = layers
    return top


def capacity(S: int, E: int, K: int, factor: float) -> int:
    """Slots per expert and sequence: ceil(S·K·factor / E), at least 8,
    rounded up to a multiple of 8."""
    cap = math.ceil(S * K * factor / E)
    return max(8, -(-cap // 8) * 8)


def moe(lp, h, n, mm):
    """Top-k routing of each sequence into per-expert slots; the pairs
    (token, k) past an expert's capacity, in token-major order, are
    dropped.  Returns the weighted expert outputs and the balance loss."""
    B, S, D = h.shape
    E, K = n["experts"], n["top_k"]
    C = capacity(S, E, K, n["capacity_factor"])
    probs = jax.nn.softmax(mm("bsd,de->bse", h, lp["router"]), axis=-1)
    gate, eid = jax.lax.top_k(probs, K)                       # (B, S, K)
    if n["renorm_topk"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    flat = eid.reshape(B, S * K)
    seen = jnp.cumsum(jax.nn.one_hot(flat, E, dtype=jnp.int32), axis=1)
    rank = jnp.take_along_axis(seen, flat[..., None], axis=2)[..., 0] - 1
    keep = rank < C
    slot = jnp.where(keep, flat * C + rank, E * C)            # E·C: dropped
    src = jnp.repeat(jnp.arange(S), K)

    def experts(hs, slots):
        slab = jnp.zeros((E * C, D), c.F32).at[slots].set(hs[src], mode="drop")
        slab = slab.reshape(E, C, D)
        a = mm("ecd,edf->ecf", slab, lp["w1"])
        b = mm("ecd,edf->ecf", slab, lp["w3"])
        out = mm("ecf,efd->ecd", jax.nn.silu(a) * b, lp["w2"])
        return out.reshape(E * C, D).at[slots].get(mode="fill", fill_value=0)

    rows = jax.vmap(experts)(h, slot)                         # (B, S·K, D)
    w = gate.reshape(B, S * K) * keep
    y = jnp.sum((rows * w[..., None]).reshape(B, S, K, D), axis=2)
    first = jnp.mean(jax.nn.one_hot(eid[..., 0], E), axis=(0, 1))
    aux = E * jnp.sum(first * jnp.mean(probs, axis=(0, 1)))
    return y, aux


def layer(lp, x, n, mm):
    B, S, _ = x.shape
    H, KV, hd, eps = n["heads"], n["kv_heads"], n["head_dim"], n["eps"]
    h = c.rmsnorm(x, lp["attn_norm"], eps)
    q = mm("bsd,df->bsf", h, lp["wq"]).reshape(B, S, H, hd)
    k = mm("bsd,df->bsf", h, lp["wk"]).reshape(B, S, KV, hd)
    v = mm("bsd,df->bsf", h, lp["wv"]).reshape(B, S, KV, hd)
    q = c.rmsnorm(q, lp["q_norm"], eps)
    k = c.rmsnorm(k, lp["k_norm"], eps)
    rope = jax.vmap(lambda t: c.rope_interleaved(t, n["theta"], hd))
    o = jax.vmap(lambda q, k, v: c.causal_attention(q, k, v, mm))(
        rope(q), rope(k), v)
    x = x + mm("bsf,fd->bsd", o.reshape(B, S, H * hd), lp["wo"])
    y, aux = moe(lp, c.rmsnorm(x, lp["ffn_norm"], eps), n, mm)
    return x + y, aux


def loss(params, tokens, labels, cfg: dict, mm):
    n = dims(cfg)
    x = params["embed"][tokens]
    aux = 0.0
    for lp in params["layers"]:
        x, a = jax.checkpoint(lambda lp, x: layer(lp, x, n, mm))(lp, x)
        aux = aux + a
    x = c.rmsnorm(x, params["final_norm"], n["eps"])
    ce = c.cross_entropy(mm("bsd,dv->bsv", x, params["lm_head"]), labels)
    return ce + n["aux_coef"] * aux / n["layers"]
