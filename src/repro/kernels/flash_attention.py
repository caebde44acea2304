"""Pallas TPU flash attention: online softmax on the MXU, with its own
backward.

The decoder's training attention (``nn.attention.attention``) runs here
on a TPU for causal self-attention longer than ``BLOCKWISE_THRESHOLD``;
every other case keeps the XLA paths of ``nn.attention``.  The score
tile lives in VMEM ((block_q, block_k) f32) and never reaches HBM: q·kᵀ
and p·v take the inputs' dtype as MXU operands (bf16 in training) and
accumulate in f32; the softmax statistics are f32.

* **Forward** — grid (B, H, q blocks, k blocks), the k axis sequential
  so the VMEM accumulators carry across it.  The residual-saving variant
  also writes the row logsumexp, the backward's only residual besides
  q, k, v and o.
* **Backward** (FlashAttention-2) — the probability tile is rebuilt from
  q, k and the logsumexp, with ``delta = rowsum(dO·O)`` computed once
  outside.  One kernel makes dK/dV with the grid over key blocks and the
  group's query heads × query blocks innermost (a GQA group's dK/dV is
  summed in VMEM), another makes dQ with key blocks innermost.
* **Block skipping** — key blocks wholly above the causal diagonal or
  below the sliding window do no work (``pl.when``), and their
  ``index_map`` is clamped to a block that is needed, so no DMA is
  issued for them.  Only blocks that straddle a mask edge build the mask.
* **GQA** — K/V keep their KV heads: the query head ``h`` reads KV head
  ``h // (H // KV)`` through the ``index_map``.

Sliding window and padding are masked the same way in every pass;
Gemma-2 logit soft-capping is supported in the forward only (its layers
take the XLA path in training).  Sequences are padded to the block size
here; padded keys are masked and padded queries are sliced away.

Validated in interpret mode against ``ref.flash_attention_ref`` (values
and ``jax.grad``) — see tests/test_kernels.py; tests/test_tpu_compile.py
compiles both passes for a described v5e.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
#: sublane rows of the row-layout residuals the dK/dV kernel reads
SUBLANES = 8
VMEM_LIMIT = 64 * 2**20


@dataclasses.dataclass(frozen=True)
class Blocks:
    """(block_q, block_k) of each of the three kernels; each a multiple of
    128 (the lane width)."""

    fwd: tuple[int, int] = (512, 512)
    dq: tuple[int, int] = (512, 512)
    dkv: tuple[int, int] = (512, 512)


#: block sizes by (key length, head size): the fastest of 256/512/1024
#: for each kernel alone on a TPU v5e (PERF.md, the block sweep); other
#: shapes take ``Blocks()``
BLOCKS: dict[tuple[int, int], Blocks] = {
    (8192, 128): Blocks(fwd=(1024, 512), dq=(1024, 1024), dkv=(1024, 1024)),
    (4096, 128): Blocks(fwd=(1024, 512), dq=(1024, 1024), dkv=(1024, 1024)),
}


@dataclasses.dataclass(frozen=True)
class _Cfg:
    causal: bool
    window: int | None
    softcap: float | None
    blocks: Blocks
    seq_k: int            # keys before padding: later ones are masked
    interpret: bool


_NT = (((1,), (1,)), ((), ()))     # a · bᵀ
_NN = (((1,), (0,)), ((), ()))     # a · b


def _lanes(x, n):
    """(rows, 128) lane-replicated → (rows, n)."""
    return jnp.tile(x, (1, n // LANES)) if n % LANES == 0 else x[:, :n]


def _key_blocks(i, bq, bk, nk, cfg):
    """First and last key block query block ``i`` needs."""
    lo, hi = 0, nk - 1
    if cfg.causal:
        hi = jnp.minimum(hi, lax.div(i * bq + bq - 1, bk))
    if cfg.window is not None:
        lo = jnp.maximum(lo, lax.div(i * bq - cfg.window + 1, bk))
    return lo, hi


def _query_blocks(j, bq, bk, nq, cfg):
    """First and last query block that key block ``j`` serves."""
    lo, hi = 0, nq - 1
    if cfg.causal:
        lo = jnp.maximum(lo, lax.div(j * bk, bq))
    if cfg.window is not None:
        hi = jnp.minimum(hi, lax.div(j * bk + bk - 2 + cfg.window, bq))
    return lo, hi


def _needs_mask(i, j, bq, bk, seq_k_pad, cfg):
    """Whether block (i, j) straddles a mask edge (a scalar bool)."""
    m = jnp.bool_(seq_k_pad > cfg.seq_k) & (j * bk + bk > cfg.seq_k)
    if cfg.causal:
        m |= j * bk + bk - 1 > i * bq
    if cfg.window is not None:
        m |= i * bq + bq - 1 - j * bk >= cfg.window
    return m


def _mask(qpos, kpos, cfg):
    ok = kpos < cfg.seq_k
    if cfg.causal:
        ok &= qpos >= kpos
    if cfg.window is not None:
        ok &= qpos - kpos < cfg.window
    return ok


def _run(needed, masked, body):
    """Run ``body(mask)`` where needed: with the mask where the block
    straddles an edge, without it elsewhere."""
    @pl.when(needed & masked)
    def _():
        body(True)

    @pl.when(needed & jnp.logical_not(masked))
    def _():
        body(False)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, cfg, scale, nk,
                seq_k_pad):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    bq, bk = cfg.blocks.fwd
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(mask):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if cfg.softcap is not None:
            s = cfg.softcap * jnp.tanh(s / cfg.softcap)
        if mask:
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(_mask(qpos, kpos, cfg), s, NEG_INF)
        m_prev = m_scr[...]                                   # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))
        l_scr[...] = alpha * l_scr[...] + p.sum(-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[...]
        pv = lax.dot_general(p.astype(v.dtype), v, _NN,
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, v.shape[-1]) + pv

    lo, hi = _key_blocks(i, bq, bk, nk, cfg)
    _run((j >= lo) & (j <= hi), _needs_mask(i, j, bq, bk, seq_k_pad, cfg),
         body)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] * _lanes(1.0 / l, acc_scr.shape[-1])
                      ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = m_scr[...] + jnp.log(l)


def _fwd_call(q, k, v, cfg: _Cfg, *, residuals: bool):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = cfg.blocks.fwd
    nq, nk = Sq // bq, Sk // bk

    def kv_map(b, h, i, j):
        lo, hi = _key_blocks(i, bq, bk, nk, cfg)
        return b, lax.div(h, G), jnp.clip(j, lo, hi), 0

    q_spec = pl.BlockSpec((None, None, bq, hd),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd), kv_map)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [q_spec]
    if residuals:
        out_shape.append(jax.ShapeDtypeStruct((B, H, Sq, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((None, None, bq, LANES),
                                      lambda b, h, i, j: (b, h, i, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, scale=1.0 / math.sqrt(hd),
                          nk=nk, seq_k_pad=Sk),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="flash_fwd",
        interpret=cfg.interpret,
    )(q, k, v)
    return (out[0], out[1][..., 0]) if residuals else out[0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, cfg, scale, nk, seq_k_pad):
    bq, bk = cfg.blocks.dq
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(mask):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, _NT,
                            preferred_element_type=jnp.float32) * scale
        if mask:
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(_mask(qpos, kpos, cfg), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, None])
        acc_scr[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                        preferred_element_type=jnp.float32)

    lo, hi = _key_blocks(i, bq, bk, nk, cfg)
    _run((j >= lo) & (j <= hi), _needs_mask(i, j, bq, bk, seq_k_pad, cfg),
         body)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dq_call(q, k, v, do, lse, delta, cfg: _Cfg):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = cfg.blocks.dq
    nq, nk = Sq // bq, Sk // bk

    def kv_map(b, h, i, j):
        lo, hi = _key_blocks(i, bq, bk, nk, cfg)
        return b, lax.div(h, G), jnp.clip(j, lo, hi), 0

    q_spec = pl.BlockSpec((None, None, bq, hd),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, hd), kv_map)
    row_spec = pl.BlockSpec((None, None, 1, bq),
                            lambda b, h, i, j: (b, h, 0, i))
    return pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, scale=1.0 / math.sqrt(hd),
                          nk=nk, seq_k_pad=Sk),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="flash_dq",
        interpret=cfg.interpret,
    )(q, k, v, do, lse[:, :, None], delta[:, :, None])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, cfg, scale, nq, n_inner,
                seq_k_pad):
    bq, bk = cfg.blocks.dkv
    j, t = pl.program_id(2), pl.program_id(3)
    i = lax.rem(t, nq)

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(mask):
        q, do = q_ref[...], do_ref[...]
        # transposed tiles: keys on sublanes, queries on lanes
        s = lax.dot_general(k_ref[...], q, _NT,
                            preferred_element_type=jnp.float32) * scale
        if mask:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            s = jnp.where(_mask(qpos, kpos, cfg), s, NEG_INF)
        p = jnp.exp(s - lse_ref[:1, :])
        dv_scr[...] += lax.dot_general(p.astype(do.dtype), do, _NN,
                                       preferred_element_type=jnp.float32)
        dp = lax.dot_general(v_ref[...], do, _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:1, :])
        dk_scr[...] += lax.dot_general(ds.astype(q.dtype), q, _NN,
                                       preferred_element_type=jnp.float32)

    lo, hi = _query_blocks(j, bq, bk, nq, cfg)
    _run((i >= lo) & (i <= hi), _needs_mask(i, j, bq, bk, seq_k_pad, cfg),
         body)

    @pl.when(t == n_inner - 1)
    def _finalize():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dkv_call(q, k, v, do, lse, delta, cfg: _Cfg):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = cfg.blocks.dkv
    nq, nk = Sq // bq, Sk // bk

    def q_block(j, t):
        lo, hi = _query_blocks(j, bq, bk, nq, cfg)
        return jnp.clip(lax.rem(t, nq), lo, hi)

    q_spec = pl.BlockSpec(
        (None, None, bq, hd),
        lambda b, g, j, t: (b, g * G + lax.div(t, nq), q_block(j, t), 0))
    row_spec = pl.BlockSpec(
        (None, None, SUBLANES, bq),
        lambda b, g, j, t: (b, g * G + lax.div(t, nq), 0, q_block(j, t)))
    kv_spec = pl.BlockSpec((None, None, bk, hd),
                           lambda b, g, j, t: (b, g, j, 0))
    rows = lambda x: jnp.broadcast_to(  # noqa: E731
        x[:, :, None], (B, H, SUBLANES, Sq))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, scale=1.0 / math.sqrt(hd),
                          nq=nq, n_inner=G * nq, seq_k_pad=Sk),
        grid=(B, KV, nk, G * nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="flash_dkv",
        interpret=cfg.interpret,
    )(q, k, v, do, rows(lse), rows(delta))


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg: _Cfg):
    return _fwd_call(q, k, v, cfg, residuals=False)


def _flash_fwd(q, k, v, cfg: _Cfg):
    o, lse = _fwd_call(q, k, v, cfg, residuals=True)
    return o, (q, k, v, o, lse)


def _flash_bwd(cfg: _Cfg, res, do):
    if cfg.softcap is not None:
        raise NotImplementedError(
            "the flash kernel's backward has no logit soft-capping; "
            "nn.attention trains soft-capped layers on the XLA path")
    q, k, v, o, lse = res
    do = do.astype(q.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
    dq = _dq_call(q, k, v, do, lse, delta, cfg)
    dk, dv = _dkv_call(q, k, v, do, lse, delta, cfg)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "blocks",
                              "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, blocks: Blocks | None = None,
                    interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) with H a multiple of KV →
    (B, H, Sq, hd).  Differentiable (``jax.custom_vjp``).

    ``blocks`` defaults to :data:`BLOCKS` for (Sk, hd); each block is cut
    to the sequence, and the sequences are padded to a multiple of them.
    """
    H, Sq, hd = q.shape[1:]
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0 and v.shape == k.shape, (q.shape, k.shape, v.shape)
    sizes = dataclasses.astuple(blocks or BLOCKS.get((Sk, hd), Blocks()))
    sizes = [(min(bq, _round_up(Sq, LANES)), min(bk, _round_up(Sk, LANES)))
             for bq, bk in sizes]
    pq = (-Sq) % math.lcm(*(bq for bq, _ in sizes))
    pk = (-Sk) % math.lcm(*(bk for _, bk in sizes))
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    cfg = _Cfg(causal=causal, window=window, softcap=softcap,
               blocks=Blocks(*sizes), seq_k=Sk, interpret=interpret)
    return _flash(q, k, v, cfg)[:, :, :Sq]
