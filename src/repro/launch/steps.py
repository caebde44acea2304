"""Jit-ready step functions: train / prefill / one-token serve."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import decoder as dec
from repro.models.config import ArchConfig
from repro.obs import scopes
from repro.optim import adamw_init, adamw_update, clip_by_global_norm


#: default microbatch size (global examples per grad-accumulation step);
#: bounds live activation memory to O(layers × microbatch × seq × d_model)
DEFAULT_MICROBATCH = 32


def make_train_step(cfg: ArchConfig, *, lr: float = 3e-4,
                    weight_decay: float = 0.1, clip: float = 1.0,
                    compute_dtype=jnp.bfloat16, remat: bool = True,
                    microbatch: int | None = DEFAULT_MICROBATCH):
    """Train step with gradient-accumulation microbatching: the batch is
    split into microbatches scanned sequentially (grads accumulate in the
    FSDP-sharded param layout), so per-layer checkpointed activations
    exist for one microbatch at a time — the same microbatching HeterPS
    uses for its pipeline stages (§3)."""

    def grads_of(params, mb):
        return jax.value_and_grad(dec.loss_fn)(
            params, cfg, mb, compute_dtype=compute_dtype, remat=remat
        )

    def train_step(params, opt_state, batch):
        B = batch["tokens"].shape[0]
        m = microbatch or B
        n_micro = max(1, B // m) if B % (m or 1) == 0 else 1
        if n_micro > 1:
            split = jax.tree.map(
                lambda x: x.reshape((n_micro, B // n_micro) + x.shape[1:]),
                batch,
            )

            def micro(carry, mb):
                gacc, lacc = carry
                loss, g = grads_of(params, mb)
                with jax.named_scope(scopes.GRAD_ACCUM):
                    gacc = jax.tree.map(jnp.add, gacc, g)
                    return (gacc, lacc + loss), None

            with jax.named_scope(scopes.GRAD_ACCUM):
                zeros = jax.tree.map(jnp.zeros_like, params)
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), split
            )
            with jax.named_scope(scopes.GRAD_ACCUM):
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = loss / n_micro
        else:
            loss, grads = grads_of(params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip)
        params, opt_state = adamw_update(
            params, grads, opt_state, lr=lr, weight_decay=weight_decay
        )
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_prefill_step(cfg: ArchConfig, *, compute_dtype=jnp.bfloat16):
    def prefill_step(params, batch):
        logits, _ = dec.forward(
            params, cfg, batch["tokens"], context=batch.get("context"),
            compute_dtype=compute_dtype, remat=False,
        )
        return logits

    return prefill_step


def make_serve_step(cfg: ArchConfig, *, compute_dtype=jnp.bfloat16):
    def serve_step(params, token, cache, index):
        return dec.decode_step(
            params, cfg, token, cache, index, compute_dtype=compute_dtype
        )

    return serve_step


def init_train_state(cfg: ArchConfig, key, *, dtype=jnp.float32):
    params = dec.init_model(cfg, key, dtype=dtype)
    return params, adamw_init(params)
