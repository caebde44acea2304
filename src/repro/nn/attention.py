"""GQA attention with RoPE, sliding window, logit soft-capping, cross-attn,
KV-cache decode, and the training paths for long sequences.

Which path :func:`attention` (training / prefill / encoder) takes:

* S ≤ ``BLOCKWISE_THRESHOLD`` — ``_sdpa_direct``, the full score matrix
  in XLA;
* causal self-attention over plain ``arange`` positions above it, with
  no soft-cap and no mesh, where ``kernels.ops.attn_impl`` resolves to
  the Pallas flash kernel (TPU, or ``interpret`` in tests) —
  ``kernels/flash_attention.py``, forward and backward, K/V unexpanded;
* everything else above it (cross-attention, non-causal encoders,
  soft-capped layers, CPU) — ``_sdpa_blockwise``, an XLA online-softmax
  scan over key blocks.

The obs counter ``attention.calls`` (labels ``path``, ``reason``) counts
at trace time which path each call took and why it fell back.

Sharding note: the XLA paths compute GQA with KV heads *expanded* to the
full head count before the score einsum, so one head axis (divisible by
the 16-wide ``model`` mesh axis for most archs) carries the tensor
parallelism; the expansion is a broadcast XLA keeps fused.  The (KV, G)
grouped form would leave both factors smaller than the mesh axis and
drop head sharding.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops as kernel_ops
from repro.kernels import paged_attention as paged_k
from repro.nn.base import apply_rope, rmsnorm, softcap
from repro.parallel import act

NEG_INF = -1e30
#: sequences longer than this use the blockwise path (bounds the live
#: logits tile instead of materializing the full S×S score matrix)
BLOCKWISE_THRESHOLD = 2048
KV_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    window: int | None = None          # sliding-window size (Gemma-2 local)
    logit_softcap: float | None = None
    rope: bool = True
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    qk_norm: bool = False              # Qwen3-style per-head RMS on q/k


def init_attention(key, d_model: int, spec: AttnSpec, *, kv_dim: int | None = None):
    kq, kk, kv, ko = jax.random.split(key, 4)
    kv_dim = kv_dim or d_model
    s = 1.0 / math.sqrt(d_model)
    p = {
        "wq": jax.random.normal(kq, (d_model, spec.n_heads * spec.head_dim)) * s,
        "wk": jax.random.normal(kk, (kv_dim, spec.n_kv_heads * spec.head_dim)) * s,
        "wv": jax.random.normal(kv, (kv_dim, spec.n_kv_heads * spec.head_dim)) * s,
        "wo": jax.random.normal(ko, (spec.n_heads * spec.head_dim, d_model))
        * (1.0 / math.sqrt(spec.n_heads * spec.head_dim)),
    }
    if spec.qk_norm:
        p["q_norm"] = jnp.ones((spec.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((spec.head_dim,), jnp.float32)
    return p


def _expand_kv(x, n_heads: int):
    """(B, S, KV, hd) → (B, S, H, hd) by repeating each KV head G times."""
    B, S, KV, hd = x.shape
    if KV == n_heads:
        return x
    g = n_heads // KV
    x = jnp.broadcast_to(x[:, :, :, None, :], (B, S, KV, g, hd))
    return x.reshape(B, S, n_heads, hd)


def _mask_bias(q_pos, k_pos, *, causal, window):
    """(B, Sq, Sk) additive mask from query/key positions."""
    # k_pos < 0 marks padding (blockwise path pads keys with -1e9)
    ok = (k_pos >= 0)[..., None, :] & jnp.ones(
        q_pos.shape[:-1] + (q_pos.shape[-1], 1), bool
    )
    d = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF)


def _sdpa_direct(q, k, v, q_pos, k_pos, spec: AttnSpec):
    """Direct attention. q,k,v: (B,S,H,hd) (kv pre-expanded)."""
    scale = 1.0 / math.sqrt(spec.head_dim)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    if spec.logit_softcap:
        logits = softcap(logits, spec.logit_softcap)
    logits += _mask_bias(q_pos, k_pos, causal=spec.causal, window=spec.window)[
        :, None
    ]
    logits = act.shard_heads(logits, axis=1)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", w, v)


def _sdpa_blockwise(q, k, v, q_pos, k_pos, spec: AttnSpec):
    """Flash-style online-softmax over KV blocks (lax.scan); the scan body
    is rematerialized (jax.checkpoint) so backward recomputes the score
    tile per block instead of saving (B,H,Sq,KV_BLOCK) per iteration.
    Same math as ``_sdpa_direct`` (tested to allclose)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(spec.head_dim)
    nblk = -(-Sk // KV_BLOCK)
    pad = nblk * KV_BLOCK - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-(10**9))
    kb = jnp.moveaxis(k.reshape(B, nblk, KV_BLOCK, H, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, KV_BLOCK, H, hd), 1, 0)
    pb = jnp.moveaxis(k_pos.reshape(B, nblk, KV_BLOCK), 1, 0)

    @jax.checkpoint
    def body(carry, blk):
        m, l, acc = carry
        kj, vj, pj = blk  # (B,KB,H,hd), (B,KB,H,hd), (B,KB)
        s = jnp.einsum("bqhd,bshd->bhqs", q, kj).astype(jnp.float32) * scale
        if spec.logit_softcap:
            s = softcap(s, spec.logit_softcap)
        s += _mask_bias(q_pos, pj, causal=spec.causal, window=spec.window)[:, None]
        s = act.shard_heads(s, axis=1)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqs,bshd->bhqd", p, vj.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = act.shard_heads(jnp.zeros((B, H, Sq, hd), jnp.float32), axis=1)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B,Sq,H,hd)


def _project_qkv(p, x, kv_x, spec: AttnSpec, q_pos, k_pos, *, expand=True):
    B, Sq, _ = x.shape
    H, KV, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(B, Sq, H, hd)
    k = (kv_x @ p["wk"]).reshape(B, kv_x.shape[1], KV, hd)
    v = (kv_x @ p["wv"]).reshape(B, kv_x.shape[1], KV, hd)
    if spec.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if spec.rope:
        q = apply_rope(q, q_pos, theta=spec.rope_theta, fraction=spec.rope_fraction)
        k = apply_rope(k, k_pos, theta=spec.rope_theta, fraction=spec.rope_fraction)
    if not expand:
        return q, k, v
    q = act.shard_heads(q, axis=2)
    k = act.shard_heads(_expand_kv(k, H), axis=2)
    v = act.shard_heads(_expand_kv(v, H), axis=2)
    return q, k, v


def _flash_fallback(spec: AttnSpec, *, impl: str, seq: int, self_attn: bool,
                    arange_positions: bool) -> str | None:
    """Why a call cannot take the flash kernel, or None if it can
    (``impl`` as ``kernels.ops.attn_impl`` resolves it)."""
    if seq <= BLOCKWISE_THRESHOLD:
        return "short"
    if impl == "xla":
        return "backend"
    if not self_attn:
        return "cross"
    if not spec.causal:
        return "non_causal"
    if spec.logit_softcap:
        return "softcap"
    if not arange_positions:
        return "positions"
    if act.current_mesh() is not None:
        return "mesh"
    return None


def attention(p, x, spec: AttnSpec, *, positions, kv_x=None, kv_positions=None,
              arange_positions: bool = False, impl: str = "auto"):
    """Full-sequence attention (training / prefill / encoder).

    x: (B, Sq, D); kv_x: cross-attention source (B, Sk, Dkv) or None.
    positions: (B, Sq) int32; ``arange_positions`` is the caller's word
    that they are ``arange(Sq)`` in every row (the flash kernel masks by
    index, not by ``positions``).  ``impl``: ``ArchConfig.attn_impl``.
    Returns (B, Sq, D).
    """
    self_attn = kv_x is None
    kv_x = x if self_attn else kv_x
    k_pos = positions if self_attn else kv_positions
    B, Sq = x.shape[:2]
    impl = kernel_ops.attn_impl(impl)
    reason = _flash_fallback(spec, impl=impl, seq=max(Sq, kv_x.shape[1]),
                             self_attn=self_attn,
                             arange_positions=arange_positions)
    if reason is None:
        obs.REGISTRY.counter("attention.calls", path="flash").inc()
        q, k, v = _project_qkv(p, x, kv_x, spec, positions, k_pos,
                               expand=False)
        o = kernel_ops.flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=True, window=spec.window,
            impl=impl)
        o = jnp.swapaxes(o, 1, 2)
    else:
        obs.REGISTRY.counter("attention.calls", path="xla",
                             reason=reason).inc()
        q, k, v = _project_qkv(p, x, kv_x, spec, positions, k_pos)
        if reason == "short":
            o = _sdpa_direct(q, k, v, positions, k_pos, spec)
        else:
            o = _sdpa_blockwise(q, k, v, positions, k_pos, spec)
    return o.reshape(B, Sq, spec.n_heads * spec.head_dim) @ p["wo"]


def init_kv_cache(batch: int, max_len: int, spec: AttnSpec, dtype=jnp.bfloat16):
    shape = (batch, max_len, spec.n_kv_heads, spec.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def decode_attention(p, x, cache, index, spec: AttnSpec, *, cross: bool = False):
    """One-token decode. x: (B, 1, D); ``cache['k']``: (B, L, KV, hd).

    The cache is a *ring buffer*: the new token writes slot ``index % L``
    and ``cache['pos']`` records true positions for masking — a
    sliding-window layer keeps ``L = window`` regardless of context length
    (this is what makes gemma2 ``long_500k`` decode fit).  Cross-attention
    (``cross=True``) reads a fixed precomputed cache and writes nothing.
    Returns (out (B,1,D), new_cache).
    """
    B = x.shape[0]
    H, KV, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    # index: scalar for self-decode; the cross path also accepts a (B,)
    # per-sequence position vector (continuous batching, ragged lengths)
    idx = jnp.asarray(index, jnp.int32)
    q_pos = jnp.broadcast_to(jnp.atleast_1d(idx)[:, None], (B, 1))
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    if spec.qk_norm:
        q = rmsnorm(q, p["q_norm"])
    if spec.rope:
        q = apply_rope(q, q_pos, theta=spec.rope_theta, fraction=spec.rope_fraction)
    if not cross:
        L = cache["k"].shape[1]
        slot = jnp.mod(index, L)
        k_new = (x @ p["wk"]).reshape(B, 1, KV, hd)
        v_new = (x @ p["wv"]).reshape(B, 1, KV, hd)
        if spec.qk_norm:
            k_new = rmsnorm(k_new, p["k_norm"])
        if spec.rope:
            k_new = apply_rope(
                k_new, q_pos, theta=spec.rope_theta, fraction=spec.rope_fraction
            )
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1
            ),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1
            ),
            "pos": jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], jnp.full((B, 1), index, jnp.int32), slot, axis=1
            ),
        }
        k, v = cache["k"], cache["v"]
        k_pos = cache["pos"]
        valid = (k_pos >= 0) & (k_pos <= index)
        if spec.window is not None:
            valid &= k_pos > index - spec.window
    else:
        k, v = cache["k"], cache["v"]
        S = k.shape[1]
        valid = jnp.ones((B, S), bool)
    # grouped GQA at decode: q-len is 1, so the (KV, G) form never needs
    # the 4-6x KV expansion the training path uses for head sharding.
    scale = 1.0 / math.sqrt(hd)
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    kq = k.astype(q.dtype)
    vq = v.astype(q.dtype)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kq).astype(jnp.float32) * scale
    if spec.logit_softcap:
        logits = softcap(logits, spec.logit_softcap)
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(vq.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, vq).reshape(B, 1, H * hd)
    return o @ p["wo"], cache


# --------------------------------------------------------------------------
# batched prefill (one forward that also yields the cacheable k/v)
# --------------------------------------------------------------------------


def _project_q(p, x, spec: AttnSpec, positions):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, spec.n_heads, spec.head_dim)
    if spec.qk_norm:
        q = rmsnorm(q, p["q_norm"])
    if spec.rope:
        q = apply_rope(q, positions, theta=spec.rope_theta,
                       fraction=spec.rope_fraction)
    return q


def prefill_attention(p, x, spec: AttnSpec, *, positions, lengths=None):
    """Full-sequence self-attention that ALSO returns the (unexpanded,
    post-rope) k/v so the caller can fill a decode cache in one shot.

    x: (B, S, D); positions: (B, S); ``lengths (B,)`` masks right-padded
    prompts — padded keys are never attended (padded *queries* produce
    garbage rows the caller discards).  Returns
    (out (B, S, D), k (B, S, KV, hd), v (B, S, KV, hd)).
    """
    B, S, _ = x.shape
    H, KV, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = _project_q(p, x, spec, positions)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if spec.qk_norm:
        k = rmsnorm(k, p["k_norm"])
    if spec.rope:
        k = apply_rope(k, positions, theta=spec.rope_theta,
                       fraction=spec.rope_fraction)
    k_pos = positions
    if lengths is not None:
        k_pos = jnp.where(positions < lengths[:, None], positions, -1)
    qs = act.shard_heads(q, axis=2)
    ke = act.shard_heads(_expand_kv(k, H), axis=2)
    ve = act.shard_heads(_expand_kv(v, H), axis=2)
    if S <= BLOCKWISE_THRESHOLD:
        o = _sdpa_direct(qs, ke, ve, positions, k_pos, spec)
    else:
        o = _sdpa_blockwise(qs, ke, ve, positions, k_pos, spec)
    return o.reshape(B, S, H * hd) @ p["wo"], k, v


def attention_with_kv(p, x, k, v, spec: AttnSpec, *, positions):
    """Cross-attention over precomputed (projected, unexpanded) k/v — the
    full-sequence analogue of ``decode_attention(cross=True)``: q is
    normed/roped at ``positions``, every key is attended (non-causal,
    no window)."""
    B, S, _ = x.shape
    H = spec.n_heads
    q = _project_q(p, x, spec, positions)
    Sk = k.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32), (B, Sk))
    cspec = dataclasses.replace(spec, causal=False, window=None)
    o = _sdpa_direct(
        act.shard_heads(q, axis=2),
        act.shard_heads(_expand_kv(k.astype(q.dtype), H), axis=2),
        act.shard_heads(_expand_kv(v.astype(q.dtype), H), axis=2),
        positions, k_pos, cspec,
    )
    return o.reshape(B, S, H * spec.head_dim) @ p["wo"]


# --------------------------------------------------------------------------
# paged KV-cache decode (shared page pool; see kernels/paged_attention.py)
# --------------------------------------------------------------------------


def init_paged_kv_cache(num_pages: int, page_size: int, spec: AttnSpec,
                        dtype=jnp.bfloat16):
    """One layer's share of the page pool: (num_pages, page_size, KV, hd)
    k/v arrays.  The page table / lengths live once per model (they are
    shared by every layer), not here."""
    shape = (num_pages, page_size, spec.n_kv_heads, spec.head_dim)
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def paged_decode_attention(p, x, cache, page_table, q_pos, spec: AttnSpec, *,
                           active=None, impl: str = "auto"):
    """One-token decode against the shared page pool.

    x: (B, 1, D); ``cache`` holds this layer's pool ({"kp", "vp"});
    page_table: (B, P) int32; q_pos: (B,) int32 — per-sequence position
    of the new token (== tokens already cached, ragged across the
    batch).  Writes the new k/v into the sequence's page, then attends
    positions ``max(0, q_pos-window+1) .. q_pos`` — reading only the
    pages that hold them.  Same GQA grouped form / rope / qk-norm /
    softcap / window semantics as :func:`decode_attention` (the dense
    oracle).  Returns (out (B, 1, D), {"kp", "vp"}).
    """
    B = x.shape[0]
    H, KV, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    if active is None:
        active = jnp.ones((B,), bool)
    pos2 = q_pos[:, None]                                   # (B, 1)
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k_new = (x @ p["wk"]).reshape(B, 1, KV, hd)
    v_new = (x @ p["wv"]).reshape(B, 1, KV, hd)
    if spec.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k_new = rmsnorm(k_new, p["k_norm"])
    if spec.rope:
        q = apply_rope(q, pos2, theta=spec.rope_theta,
                       fraction=spec.rope_fraction)
        k_new = apply_rope(k_new, pos2, theta=spec.rope_theta,
                           fraction=spec.rope_fraction)
    kp, vp = paged_k.paged_write(cache["kp"], cache["vp"], k_new[:, 0],
                                 v_new[:, 0], page_table, q_pos, active)
    qg = q[:, 0].reshape(B, KV, H // KV, hd)
    o = kernel_ops.paged_attention_decode(
        qg, kp, vp, page_table, q_pos, window=spec.window,
        softcap=spec.logit_softcap, impl=impl,
    )
    out = o.reshape(B, 1, H * hd) @ p["wo"]
    return out, {"kp": kp, "vp": vp}
