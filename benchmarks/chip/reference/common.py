"""Pieces shared by the decoder references: matmul precisions, RMSNorm,
rotary embedding, blocked causal attention, cross-entropy, clipping,
AdamW, the three-step training readings, and the forward FLOPs of the
shared layers.

Every matmul goes through a ``mm(eq, a, b)`` function, so the same code
runs as the float32 reference (``MATMULS["float32"]``) and as the
lower-precision control (``MATMULS["float8_e4m3"]``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: rows of queries per attention block; bounds the live score tile
QUERY_BLOCK = 512


def f32_matmul(eq, a, b):
    return jnp.einsum(eq, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def _e4m3(x):
    """Round to float8 e4m3, as a cast does (no scaling: values under its
    smallest normal, 2**-6, keep 3 bits less a bit per halving); the
    gradient passes straight through, so only the operands of the
    forward products and of the weight gradients are rounded."""
    x = x.astype(F32)
    q = x.astype(jnp.float8_e4m3fn).astype(F32)
    return x + jax.lax.stop_gradient(q - x)


def fp8_matmul(eq, a, b):
    return f32_matmul(eq, _e4m3(a), _e4m3(b))


#: matmul precision by name: the reference and the control below bf16
MATMULS = {"float32": f32_matmul, "float8_e4m3": fp8_matmul}


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_interleaved(x, theta, rot):
    """Rotary embedding of the first ``rot`` dims of ``x (S, H, hd)`` at
    positions 0..S-1, rotating adjacent pairs (2i, 2i+1)."""
    S, H, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv            # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x[..., :rot].reshape(S, H, rot // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    rotated = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([rotated.reshape(S, H, rot), x[..., rot:]], -1)


def causal_attention(q, k, v, mm):
    """Softmax attention of one sequence, q ``(S, H, hd)``, k/v
    ``(S, KV, hd)``; query head h reads KV head ``h // (H / KV)``.  Runs
    over blocks of queries, each rematerialized in the backward pass."""
    S, H, hd = q.shape
    g = H // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    blk = min(QUERY_BLOCK, S)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        start, qb = args
        s = mm("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        qpos = start + jnp.arange(blk)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    starts = jnp.arange(0, S, blk)
    out = jax.lax.map(block, (starts, q.reshape(S // blk, blk, H, hd)))
    return out.reshape(S, H, hd)


def attention_flops(n: dict, seq_len: int) -> float:
    """Forward FLOPs a token needs in one attention layer: the q, k, v and
    o projections, and QKᵀ and PV over (S + 1) / 2 keys on average under
    the causal mask.  2 operations per multiply-add."""
    d, H, KV, hd = n["d"], n["heads"], n["kv_heads"], n["head_dim"]
    proj = 2.0 * d * (H + 2 * KV) * hd + 2.0 * H * hd * d
    return proj + 2 * 2.0 * H * hd * (seq_len + 1) / 2


def swiglu_flops(d: int, ffn: int) -> float:
    """Forward FLOPs a token needs in one SwiGLU (three d × ffn matmuls)."""
    return 6.0 * d * ffn


def head_flops(n: dict) -> float:
    """Forward FLOPs of the LM head over the held vocabulary (not the
    program's pad rows)."""
    return 2.0 * n["d"] * n["vocab"]


def cross_entropy(logits, labels):
    logits = logits.astype(F32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def normal_init(key, shapes):
    """``{name: (shape, std)}`` → ``{name: N(0, std²) f32}``, one key per
    name folded in from ``key`` by the name's position."""
    return {name: jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            * std for i, (name, (shape, std)) in enumerate(shapes.items())}


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_names(params) -> list[str]:
    return [jax.tree_util.keystr(p, simple=True, separator=".")
            for p, _ in jax.tree_util.tree_leaves_with_path(params)]


def sq_norms(tree):
    """Per-leaf sums of squares (f32 on the device)."""
    return [jnp.sum(jnp.square(x.astype(F32))) for x in jax.tree.leaves(tree)]


def adamw_step(params, m, v, grads, t, opt):
    """Clip by global norm, then one AdamW step at count ``t`` (1-based)
    with decoupled weight decay on every leaf."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def one(p, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p
        return p - opt["lr"] * step, m, v

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)  # noqa: E731
    return pick(0), pick(1), pick(2)


def train_readings(loss_fn, init_fn, key, batches, opt, *, rows_per_block,
                   mm=f32_matmul, steps=3):
    """Train ``steps`` AdamW steps from ``init_fn(key)`` on ``batches``
    (dicts of ``tokens``/``labels`` arrays ``(B, S)``), in blocks of
    ``rows_per_block`` rows whose losses and gradients are averaged, as
    gradient accumulation does.

    Returns ``{"loss": [per step], "grad": [per-leaf norm of the first
    step's clipped gradient], "delta": [per-leaf norm of the parameters'
    change after the last step]}``, leaves in ``leaf_names`` order.
    """
    with jax.default_matmul_precision("highest"):
        params = jax.jit(init_fn)(key)
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        m, v = zeros(params), zeros(params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, tok, lab: loss_fn(p, tok, lab, mm)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=0)
        update = jax.jit(lambda p, m, v, g, t, n: adamw_step(
            p, m, v, jax.tree.map(lambda x: x / n, g), t, opt),
            donate_argnums=(0, 1, 2))
        norms = jax.jit(sq_norms)
        delta = jax.jit(lambda p, k: sq_norms(
            jax.tree.map(jnp.subtract, p, init_fn(k))))
        out = {"loss": []}
        for t in range(1, steps + 1):
            b = batches[t - 1]
            n = b["tokens"].shape[0] // rows_per_block
            acc, total = None, 0.0
            for i in range(n):
                rows = slice(i * rows_per_block, (i + 1) * rows_per_block)
                loss, g = grad_fn(params, jnp.asarray(b["tokens"][rows]),
                                  jnp.asarray(b["labels"][rows]))
                acc = g if acc is None else add(acc, g)
                total += float(loss)
            params, m, v = update(params, m, v, acc, jnp.float32(t),
                                  jnp.float32(n))
            out["loss"].append(total / n)
            if t == 1:
                out["grad"] = [math.sqrt(float(s)) / (1 - opt["b1"])
                               for s in norms(m)]
        out["delta"] = [math.sqrt(float(s)) for s in delta(params, key)]
    return out
