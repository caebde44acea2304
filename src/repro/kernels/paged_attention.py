"""Paged KV-cache decode attention — Pallas TPU kernel + page pool.

Token-by-token decode is the bandwidth-bound stage of the serving
workload (HeterPS's data-intensive layer class): every generated token
re-reads the whole KV cache, so a dense ``(B, max_len, KV, hd)`` ring
buffer charges *max-length* KV bandwidth to every sequence regardless of
its true length, and a batch slot reserves max-length HBM even while it
serves a ten-token prompt.

This module stores KV state in a **shared page pool** instead:

* ``k_pages / v_pages: (num_pages, page_size, KV, hd)`` — one pool per
  attention layer, shared by every sequence in the batch.  Page 0 is a
  reserved scratch page: inactive batch slots park their writes there so
  the decode step stays branch-free.
* ``page_table: (B, pages_per_seq) int32`` — per-sequence logical→
  physical page map (:class:`PagePool` owns allocation on the host).
  Logical position ``t`` of sequence ``b`` lives at
  ``k_pages[page_table[b, t // page_size], t % page_size]``.

The decode kernel runs on a ``(B, pages)`` grid with the page axis
sequential, online-softmax accumulators in VMEM (same algorithm as
``flash_attention``), and the page table + per-sequence positions
scalar-prefetched (SMEM) so each grid step DMAs exactly one *used* page
(all its KV heads) HBM→VMEM.  Steps past the sequence's last used page
— and, for sliding-window layers, pages wholly before the window — clamp their
block index to the previous step's, which the Pallas pipeline recognizes
as "same block" and skips the DMA: a 12-token sequence in a 4096-token
pool moves one page of KV, not 4096 rows.

On CPU (this container) the same formulation runs as a jnp
gather-over-pages (:func:`paged_decode_gather`) — the fast path the
serve loop uses — and ``interpret=True`` executes the kernel body in the
Pallas interpreter for the equivalence suite.  The dense ring-buffer
``nn.attention.decode_attention`` is kept as the ``impl="ref"`` oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: page 0 is never allocated: it is the scratch page inactive slots
#: write to (and the clamp target for defensive out-of-range indices)
SCRATCH_PAGE = 0


# --------------------------------------------------------------------------
# host-side page pool (allocation / admit / evict)
# --------------------------------------------------------------------------


class PagePool:
    """Host-side allocator for the shared KV page pool.

    Pages are identified by physical index ``1 .. num_pages-1`` (page 0
    is the reserved scratch page).  ``table`` is the dense
    ``(slots, pages_per_seq)`` page-table array the device kernels
    consume; unallocated entries point at the scratch page.

    Invariants (property-tested in ``tests/test_serve_paged.py``):
      * no physical page is owned by two live slots;
      * ``free + Σ owned == num_pages - 1`` across any admit/preempt/
        evict sequence (the free list is conserved — freed pages
        recycle; reservations withhold availability without moving
        pages, so they never break conservation).

    **Preempt/reserve seam** (overload robustness): :meth:`preempt`
    releases a live slot's pages exactly like :meth:`evict` but records
    the event — the host keeps the sequence's generated tokens and later
    re-admits it by prefilling prompt + generated-so-far.
    :meth:`reserve` withholds free pages from ordinary admissions (e.g.
    for the request whose arrival triggered a preemption, so the pages
    the victim just released cannot be raced away by another admission
    path); an admission with ``from_reservation=True`` consumes them.
    """

    def __init__(self, num_pages: int, page_size: int, slots: int,
                 pages_per_seq: int):
        assert num_pages >= 2, "need at least one allocatable page"
        self.num_pages = num_pages
        self.page_size = page_size
        self.slots = slots
        self.pages_per_seq = pages_per_seq
        # LIFO free list: recently freed (cache-warm) pages go out first
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._owned: list[list[int]] = [[] for _ in range(slots)]
        self._reserved = 0
        self.preempt_count = 0
        self.table = np.full((slots, pages_per_seq), SCRATCH_PAGE, np.int32)

    # -- queries ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def reserved_pages(self) -> int:
        return self._reserved

    @property
    def available_pages(self) -> int:
        """Free pages not withheld by a reservation."""
        return len(self._free) - self._reserved

    def owned_pages(self, slot: int) -> tuple[int, ...]:
        return tuple(self._owned[slot])

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` cache entries."""
        return max(1, -(-tokens // self.page_size))

    def can_admit(self, tokens: int, *, from_reservation: bool = False) -> bool:
        n = self.pages_for(tokens)
        avail = len(self._free) if from_reservation else self.available_pages
        return n <= self.pages_per_seq and n <= avail

    # -- mutations --------------------------------------------------------

    def reserve(self, tokens: int) -> bool:
        """Withhold the pages ``tokens`` positions need from ordinary
        admissions; ``False`` (no-op) when they are not available."""
        n = self.pages_for(tokens)
        if n > self.pages_per_seq or n > self.available_pages:
            return False
        self._reserved += n
        return True

    def cancel_reservation(self, tokens: int) -> None:
        """Return a :meth:`reserve`-d allotment to general availability."""
        n = self.pages_for(tokens)
        if n > self._reserved:
            raise ValueError(
                f"cancelling {n} pages but only {self._reserved} reserved")
        self._reserved -= n

    def admit(self, slot: int, tokens: int, *,
              from_reservation: bool = False) -> None:
        """Allocate pages covering ``tokens`` positions to an empty slot.

        ``from_reservation=True`` consumes a matching :meth:`reserve`
        allotment instead of drawing on general availability."""
        if self._owned[slot]:
            raise ValueError(f"slot {slot} already live")
        n = self.pages_for(tokens)
        if n > self.pages_per_seq:
            raise ValueError(
                f"{tokens} tokens need {n} pages > pages_per_seq="
                f"{self.pages_per_seq}")
        if from_reservation:
            if n > self._reserved:
                raise ValueError(
                    f"admit from_reservation needs {n} pages but only "
                    f"{self._reserved} are reserved")
            self._reserved -= n
        elif n > self.available_pages:
            raise MemoryError(
                f"pool exhausted: need {n} pages, {self.available_pages} "
                f"available ({len(self._free)} free, {self._reserved} "
                f"reserved)")
        if n > len(self._free):
            raise MemoryError(
                f"pool exhausted: need {n} pages, {len(self._free)} free")
        self.grow(slot, tokens)

    def preempt(self, slot: int) -> int:
        """Release a live slot's pages back to the pool so a more urgent
        request can run; the host keeps the sequence's tokens and resumes
        it later via prefill.  Returns the number of pages freed."""
        n = len(self._owned[slot])
        if n == 0:
            raise ValueError(f"slot {slot} is not live — nothing to preempt")
        self.evict(slot)
        self.preempt_count += 1
        return n

    def grow(self, slot: int, tokens: int) -> None:
        """Extend a slot's allocation to cover ``tokens`` positions
        (never draws pages below the reserved watermark)."""
        need = self.pages_for(tokens)
        if need > self.pages_per_seq:
            raise ValueError(f"{tokens} tokens exceed pages_per_seq capacity")
        if need - len(self._owned[slot]) > self.available_pages:
            # all-or-nothing: a failed grow keeps the slot's prior pages
            raise MemoryError("pool exhausted")
        while len(self._owned[slot]) < need:
            pid = self._free.pop()
            self.table[slot, len(self._owned[slot])] = pid
            self._owned[slot].append(pid)

    def evict(self, slot: int) -> None:
        """Free all of a slot's pages back to the pool."""
        while self._owned[slot]:
            self._free.append(self._owned[slot].pop())
        self.table[slot, :] = SCRATCH_PAGE


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------


def _page_window(q_pos, page_size: int, window):
    """(first, last) logical pages overlapping the live attention span
    for a query at position ``q_pos`` (valid keys: max(0, q_pos-window+1)
    .. q_pos)."""
    last = q_pos // page_size
    if window is None:
        first = jnp.zeros_like(last)
    else:
        first = jnp.maximum(q_pos - (window - 1), 0) // page_size
    return first, last


def _decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, head_ref, row_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale, page_size,
                   num_pages_seq, window, softcap_val):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = pos_ref[b]
    first, last = _page_window(q_pos, page_size, window)

    @pl.when((p >= first) & (p <= last))
    def _step():
        q = q_ref[0].astype(jnp.float32)                    # (H, hd)
        k = k_ref[0].astype(jnp.float32)                    # (ps·KV, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # (H, ps·KV)
        if softcap_val is not None:
            s = softcap_val * jnp.tanh(s / softcap_val)
        # page row r holds token r // KV of KV head r % KV; a query head
        # attends only the rows of its own KV head
        kpos = p * page_size + row_ref[...]                 # (1, ps·KV)
        ok = (head_ref[...] != 0) & (kpos <= q_pos)
        if window is not None:
            ok &= kpos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_scr[...] = l_prev * alpha + pexp.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp, v_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(p == num_pages_seq - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_pallas(q, k_pages, v_pages, page_table, q_pos, *,
                        window: int | None = None,
                        softcap: float | None = None,
                        interpret: bool = False):
    """q: (B, KV, G, hd) grouped queries; k/v_pages: (N, ps, KV, hd);
    page_table: (B, P) int32; q_pos: (B,) int32 — the new token's
    position (== tokens already cached).  Returns (B, KV, G, hd).

    Grid (B, P) with the page axis sequential.  Each step DMAs one whole
    page, every KV head of it, viewed as ``(ps·KV, hd)`` rows: the block's
    last two dimensions are the array's, which the TPU lowering requires
    (a one-head ``(ps, 1, hd)`` block is refused).  All ``H = KV·G``
    query heads score the page in one matmul and a head mask keeps each
    query head on its own KV head's rows — KV× the scores of a per-head
    grid, which a bandwidth-bound decode step does not feel.  The index
    map clamps the physical page into the live ``[first, last]`` span, so
    out-of-span steps repeat the previous block index and the pipeline
    skips their DMA — only *used* pages move HBM→VMEM.
    """
    B, KV, G, hd = q.shape
    N, ps, _, _ = k_pages.shape
    P = page_table.shape[1]
    H, R = KV * G, ps * KV
    scale = 1.0 / float(np.sqrt(hd))
    # row-major reshapes; on the TPU a pool view is a bitcast when KV
    # fills a sublane tile (KV=8 in f32), else XLA relayouts the pool
    qf = q.reshape(B, H, hd)
    kf = k_pages.reshape(N, R, hd)
    vf = v_pages.reshape(N, R, hd)
    rows = np.arange(R)
    same_head = jnp.asarray(
        (np.arange(H)[:, None] // G) == (rows[None, :] % KV), jnp.int32)
    row_pos = jnp.asarray((rows // KV)[None, :], jnp.int32)

    def page_map(b, p, pt, pos):
        first, last = _page_window(pos[b], ps, window)
        pe = jnp.clip(p, first, last)
        return (jnp.maximum(pt[b, pe], 0), 0, 0)

    def fixed(b, p, pt, pos):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_table, q_pos (SMEM)
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, p, pt, pos: (b, 0, 0)),
            pl.BlockSpec((1, R, hd), page_map),
            pl.BlockSpec((1, R, hd), page_map),
            pl.BlockSpec((H, R), fixed),
            pl.BlockSpec((1, R), fixed),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, p, pt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, page_size=ps, num_pages_seq=P,
            window=window, softcap_val=softcap,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, q_pos, qf, kf, vf, same_head, row_pos)
    return out.reshape(B, KV, G, hd)


# --------------------------------------------------------------------------
# jnp gather-over-pages formulation (the CPU fast path)
# --------------------------------------------------------------------------


def paged_decode_gather(q, k_pages, v_pages, page_table, q_pos, *,
                        window: int | None = None,
                        softcap: float | None = None):
    """Same math as the kernel as pure-jnp gathers: gather the sequence's
    table pages into (B, P·ps, KV, hd), mask to the live span, grouped
    GQA softmax.  Op order mirrors ``nn.attention.decode_attention`` so
    the dense oracle and the paged path agree to float rounding."""
    B, KV, G, hd = q.shape
    N, ps, _, _ = k_pages.shape
    P = page_table.shape[1]
    kg = k_pages[page_table].reshape(B, P * ps, KV, hd).astype(q.dtype)
    vg = v_pages[page_table].reshape(B, P * ps, KV, hd).astype(q.dtype)
    kpos = jnp.arange(P * ps, dtype=jnp.int32)[None]        # (1, P·ps)
    valid = kpos <= q_pos[:, None]
    if window is not None:
        valid &= kpos > (q_pos[:, None] - window)

    scale = 1.0 / float(np.sqrt(hd))
    logits = jnp.einsum("bkgd,bskd->bkgs", q, kg).astype(jnp.float32) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(vg.dtype)
    return jnp.einsum("bkgs,bskd->bkgd", w, vg)


# --------------------------------------------------------------------------
# pool writes (shared by decode step and batched prefill)
# --------------------------------------------------------------------------


def paged_write(k_pages, v_pages, k_new, v_new, page_table, q_pos, active):
    """Write one token's k/v (B, KV, hd) into each sequence's page for
    position ``q_pos``.  Inactive or out-of-capacity slots are steered to
    the scratch page (live pages are never touched by dead slots)."""
    B = q_pos.shape[0]
    ps = k_pages.shape[1]
    P = page_table.shape[1]
    logical = jnp.minimum(q_pos // ps, P - 1)
    pid = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    ok = active & (q_pos < P * ps)
    pid = jnp.where(ok, pid, SCRATCH_PAGE)
    row = q_pos % ps
    k_pages = k_pages.at[pid, row].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[pid, row].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_write_prefill(k_pages, v_pages, k_seq, v_seq, page_table, lengths):
    """Scatter a whole prefilled sequence (B, S, KV, hd) into the pool in
    one shot; positions ≥ the sequence's true length land on the scratch
    page (right-padded batched prefill)."""
    B, S = k_seq.shape[:2]
    ps = k_pages.shape[1]
    P = page_table.shape[1]
    t = jnp.arange(S, dtype=jnp.int32)[None]                # (1, S)
    logical = jnp.minimum(t // ps, P - 1)
    pid = jnp.take_along_axis(page_table, logical, axis=1)  # (B, S)
    ok = (t < lengths[:, None]) & (t < P * ps)
    pid = jnp.where(ok, pid, SCRATCH_PAGE)
    row = jnp.broadcast_to(t % ps, (B, S))
    k_pages = k_pages.at[pid, row].set(k_seq.astype(k_pages.dtype))
    v_pages = v_pages.at[pid, row].set(v_seq.astype(v_pages.dtype))
    return k_pages, v_pages
