"""Pallas TPU embedding-bag — fused sparse lookup + sum-pool.

The paper's data-intensive hot-spot: CTR models gather hundreds of sparse
feature rows per example and sum-pool them (§1: embedding layers process
~10 TB inputs).  TPU adaptation: the table stays in HBM and the ids are
*scalar-prefetched* (SMEM), so each grid step DMAs only the table tiles
its examples name HBM→VMEM — the gather never materializes
``(rows, dim)`` in HBM, and pooling happens in VMEM.

The TPU keeps a ``(V, dim)`` table in tiles of ``SUB`` rows (8 for
32-bit, 16 for 16-bit), and its DMA refuses a one-row slice of that
layout.  So the kernel views the table as ``(V / SUB, SUB, dim)`` —
the same bytes — DMAs the whole tile that holds each wanted row, and
picks the row in VMEM with a one-hot mask over the tile's rows.  A
table narrower than 128 lanes (CTR rows are 16 wide) is padded to 128
first: the DMA also refuses a tile slice narrower than the lane tile, and
the TPU stores such a table lane-padded anyway.

Grid: one step per tile of ``ROWS`` examples; a step starts one tile DMA
per (bag slot, example), waits for them, and reduces over the bag and
the tile rows into a ``(ROWS, 1, dim)`` output block (one example per
sublane tile: the row reduction leaves each example in its own).

Validated in interpret mode against ``ref.embedding_bag_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: examples per grid step
ROWS = 8


def _kernel(ids_ref, table_hbm, sel_ref, out_ref, buf, sem, *, bag: int,
            sub: int):
    base = pl.program_id(0) * ROWS * bag

    def copy(n, b):
        # the tile holding example n's b-th row → buf[b, n]
        return pltpu.make_async_copy(
            table_hbm.at[ids_ref[base + n * bag + b] // sub],
            buf.at[b, n], sem.at[0])

    def start(b, c):
        for n in range(ROWS):
            copy(n, b).start()
        return c

    def wait(b, c):
        for n in range(ROWS):
            copy(n, b).wait()
        return c

    jax.lax.fori_loop(0, bag, start, 0)
    jax.lax.fori_loop(0, bag, wait, 0)
    picked = (buf[...].astype(jnp.float32) * sel_ref[0]).sum(2, keepdims=True)
    out_ref[...] = picked.sum(0).astype(out_ref.dtype)      # (ROWS, 1, dim)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(ids, table, *, interpret: bool = False):
    """ids: (N, bag) int32 row ids; table: (V, dim) → (N, dim) sum-pooled."""
    N, bag = ids.shape
    V, dim = table.shape
    sub = 32 // table.dtype.itemsize
    lanes = -(-dim // 128) * 128
    pad_n, pad_v = (-N) % ROWS, (-V) % sub
    ids = jnp.pad(ids.astype(jnp.int32), ((0, pad_n), (0, 0)))
    tiles = jnp.pad(table, ((0, pad_v), (0, lanes - dim))).reshape(
        -1, sub, lanes)
    # sel[i, b, n, r, 0] = 1 where row r of the fetched tile is the id
    sel = jax.nn.one_hot(ids % sub, sub, dtype=jnp.float32)   # (Np, bag, sub)
    sel = sel.reshape(-1, ROWS, bag, sub).transpose(0, 2, 1, 3)[..., None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # flat ids (SMEM)
        grid=((N + pad_n) // ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bag, ROWS, sub, 1),
                         lambda i, ids: (i, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, 1, lanes), lambda i, ids: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((bag, ROWS, sub, lanes), table.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bag=bag, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N + pad_n, 1, lanes), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(ids.reshape(-1), tiles, sel)
    return out[:N, 0, :dim]
