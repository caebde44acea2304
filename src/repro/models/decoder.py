"""Unified block-pattern model covering all 10 assigned architectures.

One implementation handles dense / MoE / SSM / hybrid / enc-dec / VLM via
the :class:`~repro.models.config.ArchConfig` pattern.  Repeated pattern
groups are stacked on a leading ``repeats`` axis and executed with
``jax.lax.scan`` (+ ``jax.checkpoint`` remat), keeping HLO size O(pattern)
and activation memory O(depth × layer-input).

Entry points:
  * :func:`init_model`  — parameter pytree
  * :func:`forward`     — full-sequence logits (train / prefill / encoder)
  * :func:`loss_fn`     — token cross-entropy (+ MoE aux loss)
  * :func:`init_cache`  — decode cache (KV / SSM state / RWKV state)
  * :func:`decode_step` — one-token serve step against the cache
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as paged_k
from repro.models.config import ArchConfig, LayerSpec
from repro.parallel import act
from repro.nn import attention as attn_mod
from repro.nn import mamba as mamba_mod
from repro.nn import moe as moe_mod
from repro.nn import rwkv as rwkv_mod
from repro.nn.attention import AttnSpec
from repro.obs import scopes
from repro.nn.base import (
    cross_entropy_loss,
    layernorm,
    layernorm_init,
    rmsnorm,
    rmsnorm_init,
    softcap,
)

MOE_AUX_COEF = 0.01


def _attn_spec(cfg: ArchConfig, spec: LayerSpec, *, causal=True) -> AttnSpec:
    return AttnSpec(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        causal=causal, window=spec.window, logit_softcap=spec.logit_softcap,
        rope=spec.rope and cfg.pos_embed == "rope",
        rope_theta=cfg.rope_theta, rope_fraction=spec.rope_fraction,
        qk_norm=spec.qk_norm,
    )


def _norm_init(cfg: ArchConfig, d: int):
    return rmsnorm_init(d) if cfg.norm == "rms" else layernorm_init(d)


def _norm(cfg: ArchConfig, p, x):
    return rmsnorm(x, p) if cfg.norm == "rms" else layernorm(x, p)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, spec: LayerSpec):
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    p: dict[str, Any] = {"norm1": _norm_init(cfg, d)}
    aspec = _attn_spec(cfg, spec)
    if spec.mixer in ("attn", "cross_attn"):
        p["mixer"] = attn_mod.init_attention(keys[0], d, aspec)
    elif spec.mixer == "attn+cross":
        p["mixer"] = attn_mod.init_attention(keys[0], d, aspec)
        p["norm_cross"] = _norm_init(cfg, d)
        p["cross"] = attn_mod.init_attention(keys[1], d, aspec)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_mod.init_mamba(
            keys[0], d, d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv,
            expand=cfg.mamba_expand,
        )
    elif spec.mixer == "rwkv":
        p["mixer"] = rwkv_mod.init_time_mix(keys[0], d, head_size=cfg.rwkv_head_size)
    else:
        raise ValueError(spec.mixer)

    if spec.ffn != "none":
        p["norm2"] = _norm_init(cfg, d)
    if spec.ffn == "dense":
        p["ffn"] = moe_mod.init_dense_ffn(keys[2], d, cfg.d_ff)
    elif spec.ffn == "moe":
        p["ffn"] = moe_mod.init_moe(keys[2], d, cfg.moe_d_ff or cfg.d_ff,
                                    cfg.moe_experts)
    elif spec.ffn == "channel_mix":
        p["ffn"] = rwkv_mod.init_channel_mix(keys[2], d, cfg.d_ff)
    if spec.post_norm:
        p["norm_post1"] = _norm_init(cfg, d)
        if spec.ffn != "none":
            p["norm_post2"] = _norm_init(cfg, d)
    return p


def init_model(cfg: ArchConfig, key, *, dtype=jnp.float32):
    cfg.validate()
    keys = jax.random.split(key, 8)
    d, vp = cfg.d_model, cfg.padded_vocab
    params: dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (vp, d)) * (1.0 / math.sqrt(d)),
        "final_norm": _norm_init(cfg, d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (d, vp)) * (1.0 / math.sqrt(d))
    if cfg.pos_embed == "learned":
        params["pos"] = jax.random.normal(keys[2], (cfg.max_position, d)) * 0.02

    # stacked pattern blocks: tuple over pattern index, leaves (repeats, …)
    blocks = []
    for j, spec in enumerate(cfg.pattern):
        ks = jax.random.split(jax.random.fold_in(keys[3], j), cfg.repeats)
        blocks.append(jax.vmap(lambda k: _init_layer(k, cfg, spec))(ks))
    params["blocks"] = tuple(blocks)

    if cfg.encoder is not None:
        enc_spec = LayerSpec(mixer="attn", ffn="dense", rope=False)
        ks = jax.random.split(keys[4], cfg.encoder.num_layers)
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: _init_layer(k, cfg, enc_spec))(ks),
            "final_norm": _norm_init(cfg, d),
            "pos": jax.random.normal(keys[5], (cfg.encoder.frames, d)) * 0.02,
        }
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------


def _cast(p, dtype):
    """Cast float params to the compute dtype (norms etc. recompute in f32
    internally); non-float leaves pass through."""
    with jax.named_scope(scopes.CAST):
        return jax.tree.map(
            lambda a: (a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
                       else a),
            p,
        )


def _ffn_block(cfg, spec: LayerSpec, p, x, *, mode: str = "seq", cache=None):
    """norm2 → ffn → (post-norm) → residual — shared by the train,
    prefill and decode layer bodies.  ``mode``: "seq" (train/forward),
    "prefill" (also emits the rwkv channel-mix shift state), "decode"
    (steps the channel-mix against ``cache``).  Returns
    (x, moe_aux, cache_update)."""
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn == "none":
        return x, aux, {}
    with jax.named_scope(scopes.FFN):
        upd: dict[str, Any] = {}
        h = _norm(cfg, p["norm2"], x)
        if spec.ffn == "dense":
            y = moe_mod.dense_ffn(p["ffn"], h)
        elif spec.ffn == "moe":
            y, moe_aux = moe_mod.moe_ffn(
                p["ffn"], h, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor, impl=cfg.moe_impl)
            aux = aux + moe_aux["aux_loss"]
        elif spec.ffn == "channel_mix":
            if mode == "decode":
                y, upd = rwkv_mod.decode_channel_mix(p["ffn"], h, cache)
            else:
                y = rwkv_mod.channel_mix_seq(p["ffn"], h)
                if mode == "prefill":
                    upd = {"cm_shift": h[:, -1].astype(jnp.float32)}
        else:
            raise ValueError(spec.ffn)
        if spec.post_norm:
            y = _norm(cfg, p["norm_post2"], y)
        return x + y, aux, upd


def _apply_layer(cfg, spec: LayerSpec, p, x, *, positions, cross_kv=None,
                 causal=True, arange_positions=False):
    """One layer forward; ``arange_positions``: ``positions`` is
    ``arange(S)`` in every row.  Returns (x, moe_aux)."""
    p = act.gather_params(_cast(p, x.dtype), cfg)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(scopes.ATTENTION):
        h = _norm(cfg, p["norm1"], x)
        aspec = _attn_spec(cfg, spec, causal=causal)
        self_kw = dict(positions=positions, impl=cfg.attn_impl,
                       arange_positions=arange_positions)
        if spec.mixer == "attn":
            y = attn_mod.attention(p["mixer"], h, aspec, **self_kw)
        elif spec.mixer == "cross_attn":
            kv_pos = jnp.broadcast_to(
                jnp.arange(cross_kv.shape[1], dtype=jnp.int32),
                cross_kv.shape[:2])
            y = attn_mod.attention(
                p["mixer"], h, aspec, positions=positions,
                kv_x=cross_kv.astype(h.dtype), kv_positions=kv_pos,
            )
        elif spec.mixer == "attn+cross":
            y = attn_mod.attention(p["mixer"], h, aspec, **self_kw)
            if spec.post_norm:
                y = _norm(cfg, p["norm_post1"], y)
            x = x + y
            h = _norm(cfg, p["norm_cross"], x)
            kv_pos = jnp.broadcast_to(
                jnp.arange(cross_kv.shape[1], dtype=jnp.int32),
                cross_kv.shape[:2])
            y = attn_mod.attention(
                p["cross"], h, aspec, positions=positions,
                kv_x=cross_kv.astype(h.dtype), kv_positions=kv_pos,
            )
        elif spec.mixer == "mamba":
            y = mamba_mod.mamba(p["mixer"], h, d_state=cfg.mamba_d_state,
                                d_conv=cfg.mamba_d_conv)
        elif spec.mixer == "rwkv":
            y = rwkv_mod.time_mix(p["mixer"], h, head_size=cfg.rwkv_head_size)
        else:
            raise ValueError(spec.mixer)
        if spec.post_norm and spec.mixer != "attn+cross":
            y = _norm(cfg, p["norm_post1"], y)
        x = x + y
    x, ffn_aux, _ = _ffn_block(cfg, spec, p, x, mode="seq")
    return x, aux + ffn_aux


def _run_blocks(params, cfg: ArchConfig, x, *, positions, cross_kv=None,
                remat=True, arange_positions=False):
    """Scan the stacked pattern blocks over ``repeats``."""

    def group(carry, block_slice):
        x, aux = carry
        for j, spec in enumerate(cfg.pattern):
            def layer(p, x, positions, cross_kv, *, _spec=spec):
                return _apply_layer(cfg, _spec, p, x, positions=positions,
                                    cross_kv=cross_kv,
                                    arange_positions=arange_positions)

            # per-LAYER remat: backward recomputes one layer at a time, so
            # wide mixer internals (Mamba scan states, MoE buffers) never
            # coexist across the whole pattern group.
            if remat:
                layer = jax.checkpoint(layer)
            x, a = layer(block_slice[j], x, positions, cross_kv)
            x = act.shard_batch_act(x)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(group, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    return x, aux


def _encode(params, cfg: ArchConfig, context, *, remat=True):
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    enc = params["encoder"]
    x = context + enc["pos"][None, : context.shape[1]].astype(context.dtype)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2]
    )
    spec = LayerSpec(mixer="attn", ffn="dense", rope=False)

    def layer(carry, p):
        y, _ = _apply_layer(cfg, spec, p, carry, positions=positions,
                            causal=False)
        return act.shard_batch_act(y), None

    body = jax.checkpoint(layer) if remat else layer
    x, _ = jax.lax.scan(body, x, enc["blocks"])
    return _norm(cfg, enc["final_norm"], x)


def _hidden(params, cfg: ArchConfig, tokens, *, context=None,
            compute_dtype=jnp.bfloat16, remat=True):
    """Backbone forward up to the final norm. Returns (x (B,S,D), moe_aux)."""
    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["embed"][tokens].astype(compute_dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
        x = act.shard_batch_act(x)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if cfg.pos_embed == "learned":
            x = x + params["pos"][:S][None].astype(compute_dtype)

    cross_kv = None
    if cfg.encoder is not None:
        cross_kv = _encode(params, cfg, context.astype(compute_dtype), remat=remat)
    elif cfg.cross_kv_len:
        cross_kv = context.astype(compute_dtype)

    x, aux = _run_blocks(params, cfg, x, positions=positions,
                         cross_kv=cross_kv, remat=remat,
                         arange_positions=True)
    with jax.named_scope(scopes.HEAD):
        return _norm(cfg, params["final_norm"], x), aux


def forward(params, cfg: ArchConfig, tokens, *, context=None,
            compute_dtype=jnp.bfloat16, remat=True):
    """tokens: (B, S) int32; context: stub frontend embeddings (B, N, D)
    for audio/vlm archs.  Returns (logits (B, S, padded_vocab), moe_aux)."""
    x, aux = _hidden(params, cfg, tokens, context=context,
                     compute_dtype=compute_dtype, remat=remat)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(compute_dtype)
    logits = act.shard_logits(logits)
    if cfg.final_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, aux


#: sequence-chunk length for the loss head: logits materialize one
#: (B, LOSS_CHUNK, vocab) tile at a time (§Perf cycle 3 — the full
#: (B, S, 256k) f32 logits dominated gemma2's HBM bytes)
LOSS_CHUNK = 512


def loss_fn(params, cfg: ArchConfig, batch, *, compute_dtype=jnp.bfloat16,
            remat=True):
    x, aux = _hidden(
        params, cfg, batch["tokens"], context=batch.get("context"),
        compute_dtype=compute_dtype, remat=remat,
    )
    with jax.named_scope(scopes.HEAD):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(compute_dtype)
        labels = batch["labels"]
        B, S, _ = x.shape
        C = LOSS_CHUNK if (S % LOSS_CHUNK == 0 and S > LOSS_CHUNK) else S
        nc = S // C

        @jax.checkpoint
        def chunk(carry, inp):
            nll_sum, n = carry
            x_c, y_c = inp                                   # (B,C,D), (B,C)
            logits = x_c @ head
            logits = act.shard_logits(logits)
            if cfg.final_softcap:
                logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
            logits = logits.astype(jnp.float32)
            mask = y_c >= 0
            safe = jnp.maximum(y_c, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, safe[..., None],
                                       axis=-1)[..., 0]
            nll_sum = nll_sum + (((logz - gold) * mask).sum())
            return (nll_sum, n + mask.sum()), None

        xs = (
            jnp.moveaxis(x.reshape(B, nc, C, -1), 1, 0),
            jnp.moveaxis(labels.reshape(B, nc, C), 1, 0),
        )
        (nll, n), _ = jax.lax.scan(
            chunk, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), xs
        )
        loss = nll / jnp.maximum(n, 1)
        if cfg.has_moe:
            loss = loss + MOE_AUX_COEF * aux / cfg.num_layers
    return loss


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------


def _layer_cache(cfg: ArchConfig, spec: LayerSpec, batch: int, cache_len: int,
                 dtype, *, paged_pool: tuple[int, int] | None = None):
    kv = dict(
        n_kv=cfg.n_kv_heads, hd=cfg.head_dim
    )
    c: dict[str, Any] = {}
    if spec.mixer in ("attn", "attn+cross"):
        if paged_pool is not None:
            num_pages, page_size = paged_pool
            c.update(attn_mod.init_paged_kv_cache(
                num_pages, page_size, _attn_spec(cfg, spec), dtype))
        else:
            L = cache_len if spec.window is None else min(cache_len, spec.window)
            c["k"] = jnp.zeros((batch, L, kv["n_kv"], kv["hd"]), dtype)
            c["v"] = jnp.zeros((batch, L, kv["n_kv"], kv["hd"]), dtype)
            c["pos"] = jnp.full((batch, L), -1, jnp.int32)
    if spec.mixer in ("cross_attn", "attn+cross"):
        c["ck"] = jnp.zeros((batch, cfg.cross_kv_len, kv["n_kv"], kv["hd"]), dtype)
        c["cv"] = jnp.zeros((batch, cfg.cross_kv_len, kv["n_kv"], kv["hd"]), dtype)
    if spec.mixer == "mamba":
        c.update(mamba_mod.init_mamba_cache(
            batch, cfg.d_model, d_state=cfg.mamba_d_state,
            d_conv=cfg.mamba_d_conv, expand=cfg.mamba_expand, dtype=dtype,
        ))
    if spec.mixer == "rwkv":
        c.update(rwkv_mod.init_rwkv_cache(batch, cfg.d_model,
                                          head_size=cfg.rwkv_head_size))
    return c


def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, *, global_cap: int | None = None,
               page_size: int = 16, num_pages: int | None = None):
    """Decode cache pytree, stacked (repeats, …) per pattern position.

    ``global_cap`` bounds full-attention layers' KV length (used for
    gemma2's global layers at ``long_500k`` — see DESIGN.md).

    With ``cfg.kv_impl == "paged"`` the attention layers share a page
    pool instead of per-sequence ring buffers and the result is a dict
    ``{"layers", "page_table", "length", "active"}``: ``page_table``
    (batch, cache_len/page_size) maps each slot's logical pages to
    physical pool pages (identity-allocated here when ``num_pages``
    covers every slot — the continuous-batching serve loop overrides it
    from a host :class:`~repro.kernels.PagePool`), ``length`` carries
    per-sequence positions (ragged decode), and ``active`` masks live
    slots.  ``num_pages`` below full coverage *oversubscribes* the pool
    (admission control happens on the host)."""
    paged = cfg.kv_impl == "paged"
    pages_per_seq = -(-cache_len // page_size)
    if paged and num_pages is None:
        num_pages = 1 + batch * pages_per_seq
    pool = (num_pages, page_size) if paged else None
    caches = []
    for spec in cfg.pattern:
        L = cache_len
        if global_cap is not None and spec.mixer == "attn" and spec.window is None:
            L = min(L, global_cap)
        one = _layer_cache(cfg, spec, batch, L, dtype, paged_pool=pool)
        caches.append(
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.repeats,) + x.shape),
                one,
            )
        )
    if not paged:
        return tuple(caches)
    if num_pages >= 1 + batch * pages_per_seq:
        # identity allocation: slot b owns pages [1 + b·P, 1 + (b+1)·P)
        table = 1 + jnp.arange(batch * pages_per_seq,
                               dtype=jnp.int32).reshape(batch, pages_per_seq)
    else:
        table = jnp.zeros((batch, pages_per_seq), jnp.int32)  # host-assigned
    return {
        "layers": tuple(caches),
        "page_table": table,
        "length": jnp.zeros((batch,), jnp.int32),
        "active": jnp.ones((batch,), bool),
    }


def _decode_layer(cfg, spec: LayerSpec, p, x, cache, index, *, paged=None):
    """One decode layer.  ``paged = (page_table, q_pos, active)`` routes
    the self-attention through the shared page pool (ragged per-sequence
    positions); ``None`` keeps the dense ring-buffer path (scalar
    ``index``)."""
    p = act.gather_params(_cast(p, x.dtype), cfg)
    aspec = _attn_spec(cfg, spec)
    h = _norm(cfg, p["norm1"], x)
    if spec.mixer == "attn":
        if paged is not None:
            pt, q_pos, active = paged
            y, upd = attn_mod.paged_decode_attention(
                p["mixer"], h, cache, pt, q_pos, aspec, active=active)
            cache = {**cache, **upd}
        else:
            y, cache = attn_mod.decode_attention(p["mixer"], h, cache, index,
                                                 aspec)
    elif spec.mixer == "cross_attn":
        y, _ = attn_mod.decode_attention(
            p["mixer"], h, {"k": cache["ck"], "v": cache["cv"]}, index, aspec,
            cross=True,
        )
    elif spec.mixer == "attn+cross":
        if paged is not None:
            pt, q_pos, active = paged
            y, self_c = attn_mod.paged_decode_attention(
                p["mixer"], h, {k: cache[k] for k in ("kp", "vp")}, pt, q_pos,
                aspec, active=active)
            cross_index = q_pos
        else:
            y, self_c = attn_mod.decode_attention(
                p["mixer"], h, {k: cache[k] for k in ("k", "v", "pos")},
                index, aspec)
            cross_index = index
        x = x + y
        h = _norm(cfg, p["norm_cross"], x)
        y, _ = attn_mod.decode_attention(
            p["cross"], h, {"k": cache["ck"], "v": cache["cv"]}, cross_index,
            aspec, cross=True,
        )
        cache = {**cache, **self_c}
    elif spec.mixer == "mamba":
        y, cache = mamba_mod.decode_mamba(p["mixer"], h, cache,
                                          d_state=cfg.mamba_d_state,
                                          d_conv=cfg.mamba_d_conv)
    elif spec.mixer == "rwkv":
        y, tm = rwkv_mod.decode_time_mix(p["mixer"], h, cache,
                                         head_size=cfg.rwkv_head_size)
        cache = {**cache, **tm}
    if spec.post_norm and spec.mixer != "attn+cross":
        y = _norm(cfg, p["norm_post1"], y)
    x = x + y
    x, _, upd = _ffn_block(cfg, spec, p, x, mode="decode", cache=cache)
    if upd:
        cache = {**cache, **upd}
    return x, cache


def decode_step(params, cfg: ArchConfig, token, cache, index, *,
                compute_dtype=jnp.bfloat16):
    """One serve step: token (B, 1) int32 at position ``index`` (scalar),
    against ``cache``.  Returns (logits (B, 1, padded_vocab), new_cache).

    For a paged cache (``cfg.kv_impl == "paged"``) ``index`` is ignored:
    per-sequence positions come from ``cache["length"]`` (ragged across
    the batch) and only ``cache["active"]`` slots advance — inactive
    slots compute but write the pool's scratch page."""
    paged = isinstance(cache, dict)
    B = token.shape[0]
    x = params["embed"][token].astype(compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    if cfg.pos_embed == "learned":
        if paged:
            x = x + params["pos"][cache["length"]][:, None].astype(compute_dtype)
        else:
            x = x + params["pos"][index][None, None].astype(compute_dtype)

    layers = cache["layers"] if paged else cache
    pctx = (cache["page_table"], cache["length"], cache["active"]) \
        if paged else None
    # Decode unrolls the repeats (python loop): one-token HLO per layer is
    # tiny, and unrolling lets every layer's cache keep its sharding —
    # SPMD handles per-iteration dynamic-slice resharding of scanned cache
    # stacks poorly (involuntary full rematerialization).
    new_stacks = []
    for r in range(cfg.repeats):
        p_r = jax.tree.map(lambda a: a[r], params["blocks"])
        c_r = jax.tree.map(lambda a: a[r], layers)
        new_c = []
        for j, spec in enumerate(cfg.pattern):
            x, cj = _decode_layer(cfg, spec, p_r[j], x, c_r[j], index,
                                  paged=pctx)
            x = act.shard_batch_act(x)
            new_c.append(cj)
        new_stacks.append(tuple(new_c))
    new_layers = jax.tree.map(lambda *xs: jnp.stack(xs), *new_stacks)
    x = _norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(compute_dtype)
    if cfg.final_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if paged:
        new_cache = {
            **cache,
            "layers": new_layers,
            "length": cache["length"] + cache["active"].astype(jnp.int32),
        }
    else:
        new_cache = new_layers
    return logits, new_cache


# --------------------------------------------------------------------------
# batched prefill + fused decode loop (the serve hot path)
# --------------------------------------------------------------------------


def _dense_prefill_write(cache, k, v, positions, lengths):
    """Fill a dense ring buffer from a prefilled sequence in one scatter.
    Padded positions (≥ length) keep ``pos = -1`` so decode never attends
    them.  When S exceeds the ring length only the last L tokens are kept
    (uniform lengths assumed in that regime — the windowed ring is what
    makes it correct for every sequence at the same position)."""
    L = cache["k"].shape[1]
    B, S = k.shape[:2]
    if S > L:
        k, v, positions = k[:, -L:], v[:, -L:], positions[:, -L:]
    slots = positions % L
    b_ix = jnp.arange(B, dtype=jnp.int32)[:, None]
    pos = jnp.where(positions < lengths[:, None], positions, -1)
    return {
        "k": cache["k"].at[b_ix, slots].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[b_ix, slots].set(v.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[b_ix, slots].set(pos),
    }


def _prefill_layer(cfg, spec: LayerSpec, p, x, cache, positions, lengths,
                   paged):
    """One prefill layer: forward + fill this layer's decode cache."""
    p = act.gather_params(_cast(p, x.dtype), cfg)
    aspec = _attn_spec(cfg, spec)
    h = _norm(cfg, p["norm1"], x)
    if spec.mixer in ("attn", "attn+cross"):
        y, k, v = attn_mod.prefill_attention(p["mixer"], h, aspec,
                                             positions=positions,
                                             lengths=lengths)
        if paged is not None:
            kp, vp = paged_k.paged_write_prefill(
                cache["kp"], cache["vp"], k, v, paged, lengths)
            cache = {**cache, "kp": kp, "vp": vp}
        else:
            cache = {**cache,
                     **_dense_prefill_write(cache, k, v, positions, lengths)}
        if spec.mixer == "attn+cross":
            if spec.post_norm:
                y = _norm(cfg, p["norm_post1"], y)
            x = x + y
            h = _norm(cfg, p["norm_cross"], x)
            y = attn_mod.attention_with_kv(
                p["cross"], h, cache["ck"], cache["cv"], aspec,
                positions=positions)
    elif spec.mixer == "cross_attn":
        y = attn_mod.attention_with_kv(p["mixer"], h, cache["ck"],
                                       cache["cv"], aspec,
                                       positions=positions)
    elif spec.mixer == "mamba":
        y, st = mamba_mod.mamba(p["mixer"], h, d_state=cfg.mamba_d_state,
                                d_conv=cfg.mamba_d_conv, return_state=True)
        cache = {**cache, **st}
    elif spec.mixer == "rwkv":
        y, st = rwkv_mod.time_mix(p["mixer"], h,
                                  head_size=cfg.rwkv_head_size,
                                  return_state=True)
        cache = {**cache, **st}
    else:
        raise ValueError(spec.mixer)
    if spec.post_norm and spec.mixer != "attn+cross":
        y = _norm(cfg, p["norm_post1"], y)
    x = x + y
    x, _, upd = _ffn_block(cfg, spec, p, x, mode="prefill")
    if upd:
        cache = {**cache, **upd}
    return x, cache


def prefill(params, cfg: ArchConfig, tokens, cache, *, lengths=None,
            compute_dtype=jnp.bfloat16):
    """Batched prefill: ONE forward pass that fills the decode cache.

    tokens: (B, S) int32, right-padded when ``lengths (B,)`` is given —
    sample the first generated token from ``logits[b, lengths[b]-1]``.
    Returns (logits (B, S, padded_vocab), cache).

    Attention layers mask padded keys exactly; recurrent mixers (mamba /
    rwkv) fold the whole padded window into their state, so ragged
    ``lengths`` is only safe for attention-family archs — prefill
    recurrent archs at their exact prompt length (the continuous-batching
    serve loop admits per-sequence, unpadded)."""
    paged = isinstance(cache, dict)
    B, S = tokens.shape
    x = params["embed"][tokens].astype(compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.pos_embed == "learned":
        x = x + params["pos"][:S][None].astype(compute_dtype)
    x = act.shard_batch_act(x)
    lens = (jnp.full((B,), S, jnp.int32) if lengths is None
            else jnp.asarray(lengths, jnp.int32))

    layers = cache["layers"] if paged else cache
    table = cache["page_table"] if paged else None
    new_stacks = []
    for r in range(cfg.repeats):
        p_r = jax.tree.map(lambda a: a[r], params["blocks"])
        c_r = jax.tree.map(lambda a: a[r], layers)
        new_c = []
        for j, spec in enumerate(cfg.pattern):
            x, cj = _prefill_layer(cfg, spec, p_r[j], x, c_r[j], positions,
                                   lens, table)
            x = act.shard_batch_act(x)
            new_c.append(cj)
        new_stacks.append(tuple(new_c))
    new_layers = jax.tree.map(lambda *xs: jnp.stack(xs), *new_stacks)
    x = _norm(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(compute_dtype)
    if cfg.final_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if paged:
        new_cache = {**cache, "layers": new_layers,
                     "length": jnp.where(cache["active"], lens, 0)}
    else:
        new_cache = new_layers
    return logits, new_cache


def sample_logits(logits, key, *, temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0):
    """Sample next tokens from ``logits (..., V)`` → int32 ``(...)``.

    Standard filtered-softmax sampling: logits are divided by
    ``temperature``, truncated to the ``top_k`` highest (0 = off) and to
    the smallest prefix whose probability mass reaches ``top_p``
    (1.0 = off; the argmax token is always kept), then drawn via
    ``jax.random.categorical``.  Filters compose (top-k first, then
    top-p over what survives).  ``temperature``/``top_k``/``top_p`` are
    static — bake them into the jitted caller."""
    V = logits.shape[-1]
    lg = logits.astype(jnp.float32) / jnp.float32(max(temperature, 1e-6))
    if top_k and 0 < top_k < V:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p < 1.0:
        desc = jnp.sort(lg, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose preceding cumulative mass is < top_p (the
        # first is always kept: its preceding mass is 0)
        keep = (cum - probs) < top_p
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        lg = jnp.where(lg < thresh, -jnp.inf, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def decode_loop(params, cfg: ArchConfig, token, cache, index, steps: int, *,
                compute_dtype=jnp.bfloat16, key=None,
                temperature: float = 1.0, top_k: int = 0,
                top_p: float = 1.0):
    """``steps`` decode iterations as one ``lax.scan`` program —
    generated tokens accumulate ON DEVICE and transfer once, instead of a
    jit dispatch + host sync per token.

    token: (B, 1) int32 — the first token to feed (it is also the first
    token emitted, matching the serve convention that the argmax of the
    prefill logits is the first generated token).  ``index`` is the
    scalar start position for a dense cache (ignored by paged caches).

    ``key=None`` (default) decodes greedily — bit-identical to the
    pre-sampling loop.  With a PRNG key, each step draws from
    :func:`sample_logits` under ``temperature``/``top_k``/``top_p``
    (static args), splitting the key per step — fixed key ⇒ fixed
    tokens.  Returns (tokens (B, steps), next_token (B, 1), cache)."""
    V = cfg.vocab
    greedy = key is None

    def body(carry, _):
        tok, cache, idx, k = carry
        logits, cache = decode_step(params, cfg, tok, cache, idx,
                                    compute_dtype=compute_dtype)
        if greedy:
            ntok = jnp.argmax(logits[:, :, :V], axis=-1).astype(jnp.int32)
        else:
            k, sub = jax.random.split(k)
            ntok = sample_logits(logits[:, -1, :V], sub,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)[:, None]
        return (ntok, cache, idx + 1, k), tok[:, 0]

    k0 = jax.random.PRNGKey(0) if greedy else key
    (ntok, cache, _, _), toks = jax.lax.scan(
        body, (token, cache, jnp.asarray(index, jnp.int32), k0), None,
        length=steps)
    return jnp.moveaxis(toks, 0, 1), ntok, cache


def slot_cache(cache, slot: int):
    """One batch slot's view of a paged cache (B=1), for per-admission
    prefill: pool arrays (``kp``/``vp``) are shared and pass through
    whole; per-slot state (recurrent mixers, cross k/v) is sliced."""
    def per_layer(d):
        return {k: (v if k in ("kp", "vp") else v[:, slot:slot + 1])
                for k, v in d.items()}

    return {
        "layers": tuple(per_layer(d) for d in cache["layers"]),
        "page_table": cache["page_table"][slot:slot + 1],
        "length": cache["length"][slot:slot + 1],
        "active": jnp.ones((1,), bool),
    }


def merge_slot_cache(cache, sub, slot: int):
    """Merge a ``slot_cache`` view updated by :func:`prefill` back into
    the full paged cache (pool arrays replace; per-slot state scatters)."""
    def per_layer(d, s):
        return {k: (s[k] if k in ("kp", "vp")
                    else d[k].at[:, slot:slot + 1].set(s[k]))
                for k in d}

    return {
        **cache,
        "layers": tuple(per_layer(d, s)
                        for d, s in zip(cache["layers"], sub["layers"])),
        "length": cache["length"].at[slot].set(sub["length"][0]),
    }
