"""Architecture configuration — one schema covering all 10 assigned archs.

A model is a *pattern* of :class:`LayerSpec`s repeated ``repeats`` times
(total layers = ``len(pattern) × repeats``).  Params of the repeated
pattern are stacked on a leading ``repeats`` axis and iterated with
``jax.lax.scan`` so HLO size (and 512-device compile time) is
O(len(pattern)), not O(depth).
"""

from __future__ import annotations

import dataclasses
from typing import Literal

Mixer = Literal["attn", "cross_attn", "attn+cross", "mamba", "rwkv"]
Ffn = Literal["dense", "moe", "channel_mix", "none"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: Mixer = "attn"
    ffn: Ffn = "dense"
    window: int | None = None           # sliding-window attention (local)
    logit_softcap: float | None = None  # Gemma-2 attn soft-cap
    rope: bool = True
    rope_fraction: float = 1.0          # ChatGLM partial rotary
    qk_norm: bool = False               # Qwen3/OLMoE per-head q/k RMSNorm
    post_norm: bool = False             # Gemma-2 extra post-norms


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style bidirectional encoder over stub frame embeddings."""

    num_layers: int
    frames: int                         # encoder sequence length (stub input)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "audio", "vlm"]
    source: str                         # paper / model-card citation
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: tuple[LayerSpec, ...]
    repeats: int
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0                   # per-expert FFN width
    moe_capacity_factor: float = 1.25   # GShard per-group expert capacity
    #: MoE dispatch/combine data path: "auto" → fused Pallas kernels on
    #: TPU, jnp slot formulation elsewhere; "ref" pins the pure-JAX
    #: scatter/gather oracle; "interpret"/"slot"/"pallas" force a path
    #: (see repro/kernels/moe.py)
    moe_impl: str = "auto"
    #: training attention above ``nn.attention.BLOCKWISE_THRESHOLD``:
    #: "auto" → the Pallas flash kernel (forward and backward) on TPU for
    #: the calls it covers, the XLA blockwise scan elsewhere; "xla" pins
    #: the XLA paths; "interpret"/"pallas" force the kernel (resolved by
    #: kernels/ops.py ``attn_impl``)
    attn_impl: str = "auto"
    #: decode KV-cache layout: "dense" = per-sequence ring buffers (the
    #: reference oracle); "paged" = shared page pool + per-sequence page
    #: tables (kernels/paged_attention.py) — within the paged path the
    #: kernel impl resolves via kernels/ops.py impl="auto" (Pallas on
    #: TPU, jnp gather-over-pages elsewhere)
    kv_impl: str = "dense"
    # positions
    rope_theta: float = 10000.0
    pos_embed: Literal["rope", "learned", "none"] = "rope"
    max_position: int = 0               # for learned positions
    # output head
    final_softcap: float | None = None
    tie_embeddings: bool = False
    embed_scale: bool = False           # Gemma: embeddings × sqrt(d_model)
    norm: Literal["rms", "ln"] = "rms"
    # Mamba (hybrid)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # RWKV
    rwkv_head_size: int = 64
    # frontends (stub carve-out: audio conv / ViT are NOT implemented; the
    # launcher provides precomputed embeddings of this length)
    encoder: EncoderConfig | None = None
    cross_kv_len: int = 0               # image patches / audio frames
    # which input shapes this arch supports (long_500k needs sub-quadratic)
    supports_long_context: bool = False
    #: grad-accumulation microbatch (global examples); tuned down for the
    #: widest archs (§Perf) — activation liveness scales with this
    train_microbatch: int = 32

    @property
    def num_layers(self) -> int:
        return len(self.pattern) * self.repeats

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so embedding/lm_head shard
        evenly on a 16-wide model axis (whisper's 51866 needs it)."""
        return -(-self.vocab // 256) * 256

    @property
    def has_moe(self) -> bool:
        return any(s.ffn == "moe" for s in self.pattern)

    def validate(self) -> None:
        assert self.n_heads % max(self.n_kv_heads, 1) == 0, self.name
        if self.has_moe:
            assert self.moe_experts > 0 and self.moe_top_k > 0, self.name
        for s in self.pattern:
            if s.mixer in ("cross_attn", "attn+cross"):
                assert self.cross_kv_len > 0, self.name
        if self.pos_embed == "learned":
            assert self.max_position > 0, self.name
