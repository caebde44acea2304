"""Pallas TPU flash attention — blockwise online-softmax on the MXU.

TPU adaptation of the attention hot-spot (DESIGN.md §2): the score tile
lives in VMEM ((block_q, block_k) f32), K/V stream HBM→VMEM block by
block, accumulation in f32 VREGs.  Supports causal masking, sliding
window, and Gemma-2 logit soft-capping.  Block sizes default to MXU/lane
aligned (128) multiples.

Grid: (batch·heads, q_blocks, kv_blocks) with the kv dimension sequential
("arbitrary") so the VMEM scratch accumulators carry across kv steps.

Validated in interpret mode against ``ref.flash_attention_ref`` (the
pure-jnp oracle) over a shape/dtype sweep — see tests/test_kernels.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_q, block_k, n_kv, causal, window, softcap_val):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                           # (bq, bk)
    if softcap_val is not None:
        s = softcap_val * jnp.tanh(s / softcap_val)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        ok &= qpos >= kpos
    if window is not None:
        ok &= (qpos - kpos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kj == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q, k, v: (B, H, S, hd) (kv heads pre-expanded) → (B, H, Sq, hd).

    Sq must divide by block_q and Sk by block_k (pad upstream; ops.py
    handles padding + GQA expansion).
    """
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale = 1.0 / math.sqrt(hd)
    n_kv = Sk // block_k
    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * H, Sk, hd)
    vf = v.reshape(B * H, Sk, hd)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_kv=n_kv, causal=causal, window=window, softcap_val=softcap,
        ),
        grid=(B * H, Sq // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd)
