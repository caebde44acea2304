"""Jit'd dispatch wrappers for the Pallas kernels.

On a TPU runtime the compiled kernels run natively; on CPU (this
container) ``interpret=True`` executes the kernel body in Python for
correctness validation, and callers that need speed use the jnp
references.  ``auto`` picks per-backend.
"""

from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels import moe as moe_kernels
from repro.kernels import paged_attention as paged_k
from repro.kernels.embedding_bag import embedding_bag as _embedding_bag_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attn_impl(impl: str) -> str:
    """Resolve the training attention impl: ``auto`` compiles the Pallas
    flash kernel on TPU and keeps the XLA paths of ``nn.attention``
    (``xla``) elsewhere; ``interpret`` executes the kernel bodies in the
    Pallas interpreter."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    if impl not in ("xla", "interpret", "pallas"):
        raise ValueError(f"unknown attention impl {impl!r}: expected "
                         "auto/xla/interpret/pallas")
    return impl


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, impl: str = "auto"):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd), H a multiple of KV.
    Differentiable; the kernel pads ragged lengths itself."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       softcap=softcap)
    return _flash_kernel(q, k, v, causal=causal, window=window,
                         softcap=softcap, interpret=impl == "interpret")


def embedding_bag(ids, table, *, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.embedding_bag_ref(ids, table)
    return _embedding_bag_kernel(ids, table, interpret=impl == "interpret")


def _moe_impl(impl: str) -> str:
    """Resolve the MoE impl: ``auto`` compiles on TPU, otherwise runs the
    jnp slot formulation (same algorithm, fast on CPU); ``interpret``
    executes the kernel bodies in the Pallas interpreter."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "slot"
    if impl not in ("slot", "interpret", "pallas"):
        raise ValueError(
            f"unknown MoE impl {impl!r}: expected auto/slot/interpret/"
            "pallas (the scatter/gather oracle is nn.moe.moe_ffn's "
            "impl='ref', not a kernels-layer path)")
    return impl


def paged_attention_decode(q, k_pages, v_pages, page_table, q_pos, *,
                           window: int | None = None,
                           softcap: float | None = None,
                           impl: str = "auto"):
    """Paged one-token decode attention.  q: (B, KV, G, hd) grouped
    queries; k/v_pages: (num_pages, page_size, KV, hd); page_table:
    (B, P) int32; q_pos: (B,) int32.  Returns (B, KV, G, hd).

    ``auto`` compiles the Pallas kernel on TPU and runs the jnp
    gather-over-pages formulation elsewhere; ``interpret`` executes the
    kernel body in the Pallas interpreter.  The dense ring-buffer oracle
    is ``nn.attention.decode_attention`` (``ArchConfig.kv_impl="dense"``),
    not a kernels-layer path.
    """
    if impl == "gather" or (impl == "auto" and not _on_tpu()):
        return paged_k.paged_decode_gather(q, k_pages, v_pages, page_table,
                                           q_pos, window=window,
                                           softcap=softcap)
    if impl not in ("auto", "interpret", "pallas"):
        raise ValueError(
            f"unknown paged-attention impl {impl!r}: expected "
            "auto/gather/interpret/pallas")
    return paged_k.paged_decode_pallas(q, k_pages, v_pages, page_table,
                                       q_pos, window=window, softcap=softcap,
                                       interpret=impl == "interpret")


def moe_dispatch(x, eid, pos, wtok, *, num_experts: int, capacity: int,
                 top_k: int, impl: str = "auto"):
    """Capacity-slab dispatch (G,S,D)→(G,E,C,D); differentiable.

    ``impl="ref"`` is not accepted here — the reference scatter/gather
    oracle lives in :func:`repro.nn.moe.moe_ffn` (``impl="ref"``).
    """
    return moe_kernels.moe_dispatch(x, eid, pos, wtok, num_experts,
                                    capacity, top_k, _moe_impl(impl))


def moe_combine(buf, eid, pos, w, *, impl: str = "auto"):
    """Gate-weighted combine (G,E,C,D)→(G,S,D); differentiable."""
    return moe_kernels.moe_combine(buf, eid, pos, w, _moe_impl(impl))
