"""Plain float32 reference of a tiny test-only decoder whose layers are of
two kinds: pattern ``(attention, dense SwiGLU)`` then ``(attention, top-k
MoE)``, repeated, with LayerNorm (weight and bias) everywhere.  The
top-level norm's weight and bias are the flat leaves ``final_norm_w`` and
``final_norm_b``.  The MoE layer is OLMoE's reference layer
(``olmoe-1b-7b.py`` beside this file), without qk-norm."""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common as c

_spec = importlib.util.spec_from_file_location(
    "chip_reference_olmoe_for_tiny_hybrid",
    Path(__file__).with_name("olmoe-1b-7b.py"))
olmoe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(olmoe)


def dims(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "ffn": cfg["intermediate_size"], "moe_ffn": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
        "vocab_rows": cfg["vocab_rows"], "eps": cfg["layer_norm_eps"],
        "theta": cfg["rope_theta"], "renorm_topk": True,
        "capacity_factor": cfg["moe_capacity_factor"],
        "aux_coef": cfg["router_aux_loss_coef"],
    }


def is_moe(i: int) -> bool:
    return i % 2 == 1


def forward_flops_per_token(n: dict, seq_len: int) -> float:
    moe = 2.0 * n["d"] * n["experts"] + n["top_k"] * c.swiglu_flops(
        n["d"], n["moe_ffn"])
    dense = c.swiglu_flops(n["d"], n["ffn"])
    return (sum(c.attention_flops(n, seq_len) + (moe if is_moe(i) else dense)
                for i in range(n["layers"])) + c.head_flops(n))


def init_params(key, cfg: dict) -> dict:
    n = dims(cfg)
    d, hd, H, KV, E = n["d"], n["head_dim"], n["heads"], n["kv_heads"], \
        n["experts"]
    top = c.normal_init(key, {
        "embed": ((n["vocab_rows"], d), 1 / math.sqrt(d)),
        "lm_head": ((d, n["vocab_rows"]), 1 / math.sqrt(d)),
    })
    top["final_norm_w"] = jnp.ones((d,), c.F32)
    top["final_norm_b"] = jnp.zeros((d,), c.F32)
    layers = []
    for i in range(n["layers"]):
        shapes = {
            "wq": ((d, H * hd), 1 / math.sqrt(d)),
            "wk": ((d, KV * hd), 1 / math.sqrt(d)),
            "wv": ((d, KV * hd), 1 / math.sqrt(d)),
            "wo": ((H * hd, d), 1 / math.sqrt(H * hd)),
        }
        if is_moe(i):
            F = n["moe_ffn"]
            shapes.update({"router": ((d, E), 1 / math.sqrt(d)),
                           "w1": ((E, d, F), 1 / math.sqrt(d)),
                           "w3": ((E, d, F), 1 / math.sqrt(d)),
                           "w2": ((E, F, d), 1 / math.sqrt(F))})
        else:
            F = n["ffn"]
            shapes.update({"w1": ((d, F), 1 / math.sqrt(d)),
                           "w3": ((d, F), 1 / math.sqrt(d)),
                           "w2": ((F, d), 1 / math.sqrt(F))})
        lp = c.normal_init(jax.random.fold_in(key, 1000 + i), shapes)
        for norm in ("attn_norm", "ffn_norm"):
            lp[f"{norm}_w"] = jnp.ones((d,), c.F32)
            lp[f"{norm}_b"] = jnp.zeros((d,), c.F32)
        layers.append(lp)
    top["layers"] = layers
    return top


def layernorm(x, w, b, eps):
    x = x.astype(c.F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def layer(lp, x, n, mm, moe: bool):
    B, S, _ = x.shape
    H, KV, hd, eps = n["heads"], n["kv_heads"], n["head_dim"], n["eps"]
    h = layernorm(x, lp["attn_norm_w"], lp["attn_norm_b"], eps)
    q = mm("bsd,df->bsf", h, lp["wq"]).reshape(B, S, H, hd)
    k = mm("bsd,df->bsf", h, lp["wk"]).reshape(B, S, KV, hd)
    v = mm("bsd,df->bsf", h, lp["wv"]).reshape(B, S, KV, hd)
    rope = jax.vmap(lambda t: c.rope_interleaved(t, n["theta"], hd))
    o = jax.vmap(lambda q, k, v: c.causal_attention(q, k, v, mm))(
        rope(q), rope(k), v)
    x = x + mm("bsf,fd->bsd", o.reshape(B, S, H * hd), lp["wo"])
    h = layernorm(x, lp["ffn_norm_w"], lp["ffn_norm_b"], eps)
    if moe:
        y, aux = olmoe.moe(lp, h, n, mm)
        return x + y, aux
    g = jax.nn.silu(mm("bsd,df->bsf", h, lp["w1"])) * mm(
        "bsd,df->bsf", h, lp["w3"])
    return x + mm("bsf,fd->bsd", g, lp["w2"]), 0.0


def loss(params, tokens, labels, cfg: dict, mm):
    n = dims(cfg)
    x = params["embed"][tokens]
    aux = 0.0
    for i, lp in enumerate(params["layers"]):
        x, a = jax.checkpoint(
            lambda lp, x, m=is_moe(i): layer(lp, x, n, mm, m))(lp, x)
        aux = aux + a
    x = layernorm(x, params["final_norm_w"], params["final_norm_b"], n["eps"])
    ce = c.cross_entropy(mm("bsd,dv->bsv", x, params["lm_head"]), labels)
    return ce + n["aux_coef"] * aux / n["layers"]
