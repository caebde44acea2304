"""Pallas TPU fused MoE dispatch/combine — capacity-slab scatter/gather.

The MoE FFN is the heaviest layer class in the OLMoE/Qwen3-MoE configs,
and HeterPS schedules exactly these compute-intensive layers onto
accelerators — so the accelerator path has to be more than the XLA
default.  The expensive part of GShard-style MoE is not the expert
matmuls (dense einsums the MXU already loves) but the *data movement*
around them: the reference path materializes a K-times-repeated copy of
the tokens, scatter-adds it into the ``(E, C, D)`` capacity slabs, and
later gathers an ``(N·K, D)`` intermediate back out.

Here the routing *metadata* (which token fills which expert slot) is
computed once with cheap integer ops (:func:`slot_maps`), and the heavy
D-dimensional row movement happens in two Pallas kernels:

* **dispatch** — grid ``(G, E, C / tile)``: each step DMAs its slots'
  source token rows HBM→VMEM (row ids scalar-prefetched from the slot
  map) and writes them, scaled by the slot weights, into the slab.  The
  repeated ``(G, N·K, D)`` source and the scatter pass never exist in
  HBM.
* **combine** — grid ``(G, S / TOKENS)``: each step DMAs the K expert
  rows of each of its tokens and sums them gate-weighted in f32; the
  ``(G, N·K, D)`` gathered intermediate never materializes.

Both keep the gathered operand in HBM (``pl.ANY``) and move rows with
explicit DMAs over a ``(rows, D / 128, 128)`` view (:func:`_row_view`):
the TPU refuses a one-row ``(1, D)`` block or DMA slice of the tiled
``(rows, D)`` layout, whose second-minor dimension comes in tiles of 8.

Gradients: both ops are linear in their float inputs and each one's
transpose is the other, so ``custom_vjp`` implements dispatch's backward
as a combine (and vice versa) — the backward pass reuses the same
kernels.  ``combine``'s weight gradient needs the gathered expert rows
and falls back to an XLA gather (same bytes the forward reference path
moves anyway); ``dispatch`` treats its weight as a constant because the
model only ever passes the non-differentiable keep mask there.

On CPU (this container) ``impl="slot"`` runs the same slot-map
formulation as pure-jnp gathers — measurably faster than the reference
scatter/gather (see ``bench_kernels``) — and ``impl="interpret"``
executes the kernel bodies in the Pallas interpreter for the
equivalence suite.  Compiled Pallas runs on a real TPU backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------------
# routing metadata (cheap integer ops, shared by every impl)
# --------------------------------------------------------------------------


def slot_maps(eid, pos, keep, *, num_experts: int, capacity: int):
    """Invert the token→slot routing into per-slot source maps.

    eid, pos, keep: ``(G, NK)`` — expert id, position-in-expert and keep
    mask per (token, k) slot, with ``NK = S·K`` and source token
    ``s = nk // K``.  Returns ``slot_nk (G, E, C) int32`` — the flat
    (token, k) index claiming each slot, ``-1`` for empty slots.

    Kept slots are claimed by exactly one (token, k) pair: ``pos`` is an
    exclusive running count per (group, expert), so indices are unique;
    dropped pairs are steered to the out-of-range position ``C`` and
    discarded by ``mode="drop"``.
    """
    G, NK = eid.shape
    E, C = num_experts, capacity

    pos_sc = jnp.where(keep, pos, C)  # C is out of bounds -> dropped
    nk_ids = jnp.broadcast_to(jnp.arange(NK, dtype=jnp.int32), (G, NK))

    def per_group(e_g, p_g, nk_g):
        empty = jnp.full((E, C), -1, jnp.int32)
        return empty.at[e_g, p_g].set(nk_g, mode="drop")

    slot_nk = jax.vmap(per_group)(eid, pos_sc, nk_ids)
    return slot_nk


def slot_sources(slot_nk, *, top_k: int):
    """slot_nk ``(G, E, C)`` flat (token,k) ids → token row ids (−1 kept)."""
    return jnp.where(slot_nk >= 0, slot_nk // top_k, -1)


def slot_weights(slot_nk, wtok):
    """Scatter per-(token,k) weights ``wtok (G, NK)`` onto the slots.

    Empty slots get weight 0, which is what makes the ``max(src, 0)``
    row-select in the kernels safe.
    """
    G, NK = wtok.shape
    safe = jnp.maximum(slot_nk, 0)
    w = jnp.take_along_axis(
        wtok, safe.reshape(G, -1), axis=1
    ).reshape(slot_nk.shape)
    return jnp.where(slot_nk >= 0, w, 0.0).astype(wtok.dtype)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------


#: tokens per combine grid step
TOKENS = 8


def _row_tile(n: int, cap: int = 32) -> int:
    """Largest divisor of ``n`` that is at most ``cap``: slab slots per
    dispatch grid step."""
    return max(t for t in range(1, min(n, cap) + 1) if n % t == 0)


def _row_view(a):
    """``(..., D)`` → ``(..., D / L, L)`` with ``L = 128`` when it divides
    D (else ``L = D``).  The row index becomes an untiled leading
    dimension, so a single row is a whole-tile slice the TPU's DMA
    accepts; a ``(1, D)`` slice of the tiled ``(rows, D)`` layout is
    refused.  XLA relayouts the array once on the way in."""
    D = a.shape[-1]
    L = 128 if D % 128 == 0 else D
    return a.reshape(a.shape[:-1] + (D // L, L))


def _dispatch_kernel(src_ref, x_hbm, w_ref, out_ref, rows, sem, *,
                     num_experts: int, capacity: int, tile: int):
    g, e, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base = (g * num_experts + e) * capacity + ci * tile

    def copy(j):
        src = jnp.maximum(src_ref[base + j], 0)
        return pltpu.make_async_copy(x_hbm.at[g, src], rows.at[j], sem.at[0])

    def start(j, c):
        copy(j).start()
        return c

    def wait(j, c):
        copy(j).wait()
        return c

    jax.lax.fori_loop(0, tile, start, 0)
    jax.lax.fori_loop(0, tile, wait, 0)
    out_ref[0, 0] = (rows[...].astype(jnp.float32) * w_ref[0, 0]
                     ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_experts", "capacity",
                                             "interpret"))
def dispatch_pallas(x, slot_src, slot_w, *, num_experts: int, capacity: int,
                    interpret: bool = False):
    """x: (G, S, D); slot_src/slot_w: (G, E, C) → slabs (G, E, C, D).

    Grid ``(G, E, C / tile)``: the flat source-row map is scalar-prefetched
    (SMEM) and ``x`` stays in HBM, so each step DMAs exactly its ``tile``
    source rows HBM→VMEM, scales them by their slot weights and writes
    them into the slab — the K-repeated token buffer of the reference
    path never materializes.
    """
    G, S, D = x.shape
    E, C = num_experts, capacity
    tile = _row_tile(C)
    xr = _row_view(x)                                    # (G, S, D/L, L)
    R, L = xr.shape[2:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # flat slot_src (int32)
        grid=(G, E, C // tile),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, tile, 1, 1),
                         lambda g, e, c, src: (g, e, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile, R, L),
                               lambda g, e, c, src: (g, e, c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((tile, R, L), x.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, num_experts=E, capacity=C,
                          tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, E, C, R, L), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(slot_src.reshape(-1), xr,
      slot_w.astype(jnp.float32).reshape(G, E, C, 1, 1))
    return out.reshape(G, E, C, D)


def _combine_kernel(eid_ref, pos_ref, buf_hbm, w_ref, out_ref, rows, sem, *,
                    top_k: int):
    g, t0 = pl.program_id(0), pl.program_id(1)
    base = (g * pl.num_programs(1) + t0) * TOKENS * top_k

    def copy(t, k):
        i = base + t * top_k + k
        return pltpu.make_async_copy(buf_hbm.at[g, eid_ref[i], pos_ref[i]],
                                     rows.at[k, t], sem.at[0])

    def start(t, c):
        for k in range(top_k):
            copy(t, k).start()
        return c

    def wait(t, c):
        for k in range(top_k):
            copy(t, k).wait()
        return c

    jax.lax.fori_loop(0, TOKENS, start, 0)
    jax.lax.fori_loop(0, TOKENS, wait, 0)
    acc = rows[0].astype(jnp.float32) * w_ref[0, 0]
    for k in range(1, top_k):
        acc += rows[k].astype(jnp.float32) * w_ref[0, k]
    out_ref[0] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_pallas(buf, eid, pos, w, *, interpret: bool = False):
    """buf: (G, E, C, D); eid/pos/w: (G, S, K) → tokens (G, S, D).

    Grid ``(G, S / TOKENS)``: ``buf`` stays in HBM and the (eid, pos)
    routing is scalar-prefetched, so each step DMAs the ``TOKENS · K``
    expert rows its tokens need HBM→VMEM and sums them gate-weighted in
    f32 — the (G, S, K, D) gather intermediate never exists.  S is padded
    to whole token tiles with zero-weight rows.
    """
    G, E, C, D = buf.shape
    _, S, K = eid.shape
    pad = (-S) % TOKENS
    Sp = S + pad
    eid_p, pos_p, w_p = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                         for a in (eid, pos, w))
    w_t = jnp.moveaxis(w_p.astype(jnp.float32), 2, 1)[..., None, None]
    br = _row_view(buf)                                  # (G, E, C, D/L, L)
    R, L = br.shape[3:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # flat eid, pos (int32)
        grid=(G, Sp // TOKENS),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, K, TOKENS, 1, 1),
                         lambda g, t, e, p: (g, 0, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TOKENS, R, L),
                               lambda g, t, e, p: (g, t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((K, TOKENS, R, L), buf.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    out = pl.pallas_call(
        functools.partial(_combine_kernel, top_k=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, Sp, R, L), buf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(eid_p.reshape(-1), pos_p.reshape(-1), br, w_t)
    return out.reshape(G, Sp, D)[:, :S]


# --------------------------------------------------------------------------
# jnp slot formulation (the CPU fast path; same algorithm as the kernels)
# --------------------------------------------------------------------------


def dispatch_slot(x, slot_src, slot_w):
    """Gather-formulated dispatch: slab row = slot_w · x[slot_src]."""
    G, S, D = x.shape

    def per_group(x_g, src_g, w_g):
        rows = x_g[jnp.maximum(src_g, 0)]                  # (E, C, D)
        return rows * w_g[..., None].astype(x_g.dtype)

    return jax.vmap(per_group)(x, slot_src, slot_w)


def combine_slot(buf, eid, pos, w):
    """Gather + gate-weighted sum over k (identical math to the kernel)."""

    def per_group(b_g, e_g, p_g, w_g):
        rows = b_g[e_g, p_g]                               # (S, K, D)
        return (rows * w_g[..., None].astype(b_g.dtype)).sum(axis=1)

    return jax.vmap(per_group)(buf, eid, pos, w)


# --------------------------------------------------------------------------
# differentiable entry points (custom_vjp: dispatchᵀ = combine)
# --------------------------------------------------------------------------


def _dispatch_impl(x, eid, pos, wtok, *, num_experts, capacity, top_k, impl):
    slot_nk = slot_maps(eid, pos, wtok != 0, num_experts=num_experts,
                        capacity=capacity)
    slot_src = slot_sources(slot_nk, top_k=top_k)
    slot_w = slot_weights(slot_nk, wtok)
    if impl == "interpret" or impl == "pallas":
        return dispatch_pallas(x, slot_src, slot_w, num_experts=num_experts,
                               capacity=capacity,
                               interpret=impl == "interpret")
    return dispatch_slot(x, slot_src, slot_w)


def _combine_impl(buf, eid, pos, w, *, impl):
    if impl == "interpret" or impl == "pallas":
        return combine_pallas(buf, eid, pos, w, interpret=impl == "interpret")
    return combine_slot(buf, eid, pos, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def moe_dispatch(x, eid, pos, wtok, num_experts, capacity, top_k, impl):
    """Differentiable dispatch: (G,S,D) tokens → (G,E,C,D) capacity slabs.

    eid/pos: ``(G, S·K)`` int32 routing; wtok: ``(G, S·K)`` per-(token,k)
    weight — the keep mask in the forward model, treated as a constant
    under differentiation (it is a 0/1 comparison output).
    """
    return _dispatch_impl(x, eid, pos, wtok, num_experts=num_experts,
                          capacity=capacity, top_k=top_k, impl=impl)


def _moe_dispatch_fwd(x, eid, pos, wtok, num_experts, capacity, top_k, impl):
    out = _dispatch_impl(x, eid, pos, wtok, num_experts=num_experts,
                         capacity=capacity, top_k=top_k, impl=impl)
    return out, (eid, pos, wtok, x.shape)


def _moe_dispatch_bwd(num_experts, capacity, top_k, impl, res, dbuf):
    eid, pos, wtok, x_shape = res
    G, S, D = x_shape
    K = eid.shape[1] // S
    # dispatch is linear in x with matrix Pᵀ; its transpose is combine:
    # dx[s] = Σ_k wtok[s,k] · dbuf[eid, pos].  Dropped pairs carry
    # pos ≥ C — clamp them to slot 0 (their weight is 0) so the combine
    # kernel's block index never leaves the (E, C) slab: compiled Pallas
    # does not clamp, unlike the CPU gather paths.
    safe_pos = jnp.where(wtok != 0, pos, 0)
    dx = _combine_impl(
        dbuf,
        eid.reshape(G, S, K), safe_pos.reshape(G, S, K),
        wtok.reshape(G, S, K), impl=impl,
    ).astype(jnp.result_type(dbuf))
    return dx, None, None, jnp.zeros_like(wtok)


moe_dispatch.defvjp(_moe_dispatch_fwd, _moe_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def moe_combine(buf, eid, pos, w, impl):
    """Differentiable combine: (G,E,C,D) slabs → (G,S,D) tokens.

    eid/pos/w: ``(G, S, K)``; w is the (differentiable) gate·keep weight.
    """
    return _combine_impl(buf, eid, pos, w, impl=impl)


def _moe_combine_fwd(buf, eid, pos, w, impl):
    return _combine_impl(buf, eid, pos, w, impl=impl), (buf, eid, pos, w)


def _moe_combine_bwd(impl, res, dy):
    buf, eid, pos, w = res
    G, E, C, D = buf.shape
    _, S, K = eid.shape
    # combineᵀ = dispatch: dbuf[e,c] = w[s,k] · dy[s] for the slot's owner
    keep = w != 0
    dbuf = _dispatch_impl(
        dy, eid.reshape(G, S * K), pos.reshape(G, S * K),
        jnp.where(keep, w, 0.0).reshape(G, S * K).astype(jnp.float32),
        num_experts=E, capacity=C, top_k=K, impl=impl,
    ).astype(buf.dtype)
    # dw[s,k] = ⟨dy[s], buf[eid, pos]⟩ — needs the gathered rows; XLA
    # gather here (backward only; same bytes the fwd reference moves)
    def per_group(b_g, e_g, p_g, dy_g):
        rows = b_g[e_g, p_g]                               # (S, K, D)
        return jnp.einsum("skd,sd->sk", rows.astype(jnp.float32),
                          dy_g.astype(jnp.float32))

    dw = jax.vmap(per_group)(buf, eid, pos, dy)
    dw = jnp.where(keep, dw, 0.0).astype(w.dtype)
    return dbuf, None, None, dw


moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)
