"""Plain float32 reference of ChatGLM3-6B-32K (THUDM/chatglm3-6b-32k),
as the configuration file runs it.

Per layer: RMSNorm, grouped-query attention (32 query heads over 2 KV
groups, head 128) with rotary positions on the first half of each head
at base 10000 · rope_ratio, residual; RMSNorm, SwiGLU, residual.  Then
the final RMSNorm, the LM head over the vocabulary slice and the mean
token cross-entropy.

The file's ``departures`` are followed where the program departs from the
published model: no QKV bias, eps 1e-6, pad rows in the vocabulary.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common as c


def dims(cfg: dict) -> dict:
    dep = cfg["departures"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["multi_query_group_num"],
        "head_dim": cfg["kv_channels"], "ffn": cfg["ffn_hidden_size"],
        "layers": cfg["num_layers"],
        "vocab": cfg["padded_vocab_size"],
        "vocab_rows": dep["vocab_rows"]["as_run"],
        "eps": dep["layernorm_epsilon"]["as_run"],
        "theta": 10000.0 * cfg["rope_ratio"],
        "rot": cfg["kv_channels"] // 2,
    }


def forward_flops_per_token(n: dict, seq_len: int) -> float:
    """Forward FLOPs a token requires: attention and SwiGLU in each
    layer, then the head."""
    return (n["layers"] * (c.attention_flops(n, seq_len)
                           + c.swiglu_flops(n["d"], n["ffn"]))
            + c.head_flops(n))


def init_params(key, cfg: dict) -> dict:
    n = dims(cfg)
    d, hd, H, KV, F = n["d"], n["head_dim"], n["heads"], n["kv_heads"], n["ffn"]
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
            "w1": ((d, F), 1 / math.sqrt(d)),
            "w3": ((d, F), 1 / math.sqrt(d)),
            "w2": ((F, d), 1 / math.sqrt(F)),
        })
        lp["attn_norm"] = jnp.ones((d,), c.F32)
        lp["ffn_norm"] = jnp.ones((d,), c.F32)
        layers.append(lp)
    top["layers"] = layers
    return top


def layer(lp, x, n, mm):
    B, S, _ = x.shape
    H, KV, hd, eps = n["heads"], n["kv_heads"], n["head_dim"], n["eps"]
    h = c.rmsnorm(x, lp["attn_norm"], eps)
    q = mm("bsd,df->bsf", h, lp["wq"]).reshape(B, S, H, hd)
    k = mm("bsd,df->bsf", h, lp["wk"]).reshape(B, S, KV, hd)
    v = mm("bsd,df->bsf", h, lp["wv"]).reshape(B, S, KV, hd)
    rope = jax.vmap(lambda t: c.rope_interleaved(t, n["theta"], n["rot"]))
    o = jax.vmap(lambda q, k, v: c.causal_attention(q, k, v, mm))(
        rope(q), rope(k), v)
    x = x + mm("bsf,fd->bsd", o.reshape(B, S, H * hd), lp["wo"])
    h = c.rmsnorm(x, lp["ffn_norm"], eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, lp["w1"])) * mm("bsd,df->bsf", h, lp["w3"])
    return x + mm("bsf,fd->bsd", g, lp["w2"])


def loss(params, tokens, labels, cfg: dict, mm):
    n = dims(cfg)
    x = params["embed"][tokens]
    for lp in params["layers"]:
        x = jax.checkpoint(lambda lp, x: layer(lp, x, n, mm))(lp, x)
    x = c.rmsnorm(x, params["final_norm"], n["eps"])
    return c.cross_entropy(mm("bsd,dv->bsv", x, params["lm_head"]), labels)
