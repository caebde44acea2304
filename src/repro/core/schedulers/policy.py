"""Policy networks for RL scheduling (HeterPS §5.2, Fig. 3) — pure JAX.

The LSTM reads one layer per step.  Step ``l``'s input is the layer's five
features (Fig. 3: one-hot index, one-hot layer type, input size, weight
size, communication time) concatenated with the one-hot of the previous
action — this gives the autoregressive conditioning
``P(a_l | a_{(l-1):1}; θ)`` of Formula 14.  The per-step output is a
``T``-way softmax over resource types.

An Elman RNN cell with the same interface implements the paper's RL-RNN
baseline (which "suffers from the vanishing gradients problem", §6.2).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.profiles import LAYER_KINDS, LayerProfile

MAX_LAYERS = 64  # one-hot index capacity (paper models have <= 20 layers)


def layer_features(
    profiles: Sequence[LayerProfile],
    *,
    pad_to: int | None = None,
    return_mask: bool = False,
) -> np.ndarray | tuple[np.ndarray, np.ndarray]:
    """(L, F) feature matrix — the five Fig.-3 features per layer.

    ``pad_to`` appends all-zero rows up to a common layer count so several
    models can share one vmapped search; ``return_mask`` additionally
    returns the (pad_to,) bool validity mask those searches need to zero
    padded steps out of log-probs (see ``plan_logp``).

    Models deeper than :data:`MAX_LAYERS` are rejected: the index one-hot
    would silently alias every layer past slot ``MAX_LAYERS - 1`` onto one
    column, destroying the autoregressive position signal.  Widen
    ``MAX_LAYERS`` for deeper models.
    """
    L = len(profiles)
    if L > MAX_LAYERS:
        raise ValueError(
            f"{L} layers exceed the policy's index one-hot capacity "
            f"MAX_LAYERS={MAX_LAYERS}; layers {MAX_LAYERS}..{L - 1} would "
            f"alias onto one slot — raise policy.MAX_LAYERS"
        )
    P = pad_to if pad_to is not None else L
    if P < L:
        raise ValueError(f"pad_to={P} < {L} layers")
    kind_ix = {k: i for i, k in enumerate(LAYER_KINDS)}
    feats = np.zeros((P, MAX_LAYERS + len(LAYER_KINDS) + 3), dtype=np.float32)
    for i, p in enumerate(profiles):
        feats[i, i] = 1.0                                            # index
        feats[i, MAX_LAYERS + kind_ix.get(p.kind, 0)] = 1.0          # type
        base = MAX_LAYERS + len(LAYER_KINDS)
        feats[i, base + 0] = math.log1p(p.input_bytes) / 20.0        # input size
        feats[i, base + 1] = math.log1p(p.weight_bytes) / 20.0       # weight size
        feats[i, base + 2] = math.log1p(1e6 * float(np.mean(p.odt))) / 20.0  # comm
    if return_mask:
        return feats, np.arange(P) < L
    return feats


def init_lstm(key, in_dim: int, hidden: int, num_types: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(hidden)
    return {
        "wx": jax.random.uniform(k1, (in_dim, 4 * hidden), minval=-s, maxval=s),
        "wh": jax.random.uniform(k2, (hidden, 4 * hidden), minval=-s, maxval=s),
        "b": jnp.zeros((4 * hidden,)),
        "wo": jax.random.uniform(k3, (hidden, num_types), minval=-s, maxval=s),
        "bo": jnp.zeros((num_types,)),
        "h0": jnp.zeros((hidden,)),
        "c0": jnp.zeros((hidden,)),
    }


def init_rnn(key, in_dim: int, hidden: int, num_types: int):
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(hidden)
    return {
        "wx": jax.random.uniform(k1, (in_dim, hidden), minval=-s, maxval=s),
        "wh": jax.random.uniform(k2, (hidden, hidden), minval=-s, maxval=s),
        "b": jnp.zeros((hidden,)),
        "wo": jax.random.uniform(k3, (hidden, num_types), minval=-s, maxval=s),
        "bo": jnp.zeros((num_types,)),
        "h0": jnp.zeros((hidden,)),
    }


def _lstm_step(params, carry, zx):
    """``zx`` is the step's input contribution ``x @ wx``, precomputed
    outside the scan (see :func:`_input_proj`)."""
    h, c = carry
    z = zx + h @ params["wh"] + params["b"]
    i, f, g, o = jnp.split(z, 4)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jax.nn.sigmoid(o)
    c = f * c + i * jnp.tanh(g)
    h = o * jnp.tanh(c)
    return (h, c), h


def _rnn_step(params, carry, zx):
    (h,) = carry
    h = jnp.tanh(zx + h @ params["wh"] + params["b"])
    return (h,), h


def _input_proj(params, feats):
    """Hoist the input matmul out of the recurrence.

    Step ``l``'s input is ``concat(feats[l], one_hot(prev_a))``; its
    contribution to the pre-activation is ``feats[l] @ wx_f + wx_a[prev_a]``
    where ``wx_f``/``wx_a`` split ``wx``'s rows.  The feature half is the
    same for every step of every sampled plan, so it is computed once as
    one (L, 4H) matmul; the action half is a single row gather inside the
    scan (the one-hot picks exactly one row).  Returns ``(xf, wx_a)``.
    """
    F = feats.shape[1]
    return feats @ params["wx"][:F], params["wx"][F:]


def _initial_carry(params, cell: str):
    if cell == "lstm":
        return (params["h0"], params["c0"])
    return (params["h0"],)


def _step_mask(feats, mask):
    """(L,) float validity weights for padded layer rows (1.0 = real).

    Explicit ``feats.dtype`` keeps policy math in float32 even when the
    caller traces under ``jax.enable_x64(True)`` (the fused
    search runs its cost side in f64 but the policy side must stay f32 to
    match the unfused per-round path).
    """
    if mask is None:
        return jnp.ones(feats.shape[0], dtype=feats.dtype)
    return mask.astype(feats.dtype)


@partial(jax.jit, static_argnames=("cell", "num_types"))
def sample_plan(params, feats, key, *, cell: str, num_types: int,
                temperature=1.0, mask=None):
    """Sample one plan autoregressively; returns (actions, sum log-prob).

    ``temperature`` flattens the *sampling* distribution only; the
    returned log-prob is the plan's log-probability under the untempered
    policy — the quantity Formula 15's gradient differentiates (it equals
    the sampling log-prob when ``temperature == 1``).  This lets the fused
    search take the REINFORCE gradient by ``jax.vjp`` straight through
    this pass instead of re-running a teacher-forced one.

    ``mask`` (optional, (L,) bool) marks real layer rows; padded rows still
    sample an action (keeping the RNG stream independent of padding) but
    contribute zero log-prob.
    """
    step = _lstm_step if cell == "lstm" else _rnn_step
    xf, wx_a = _input_proj(params, feats)

    def body(carry, inp):
        state, prev_a, k = carry
        zf, m = inp
        state, h = step(params, state, zf + wx_a[prev_a])
        logits = h @ params["wo"] + params["bo"]
        k, ks = jax.random.split(k)
        # int32-explicit: under x64 tracing, categorical would return int64
        # and break the scan carry's dtype against the int32 initial action
        a = jax.random.categorical(ks, logits / temperature).astype(jnp.int32)
        logp = jax.nn.log_softmax(logits)[a] * m
        return (state, a, k), (a, logp)

    carry = (_initial_carry(params, cell), jnp.int32(0), key)
    _, (actions, logps) = jax.lax.scan(
        body, carry, (xf, _step_mask(feats, mask))
    )
    return actions, logps.sum()


@partial(jax.jit, static_argnames=("cell", "num_types"))
def greedy_plan(params, feats, *, cell: str, num_types: int):
    """Argmax decode — the final scheduling decision (§5.2).

    Callers with padded feature rows truncate the decoded actions to the
    real layer count (padding sits at the end, so real steps are
    unaffected by it).
    """
    step = _lstm_step if cell == "lstm" else _rnn_step
    xf, wx_a = _input_proj(params, feats)

    def body(carry, zf):
        state, prev_a = carry
        state, h = step(params, state, zf + wx_a[prev_a])
        a = jnp.argmax(h @ params["wo"] + params["bo"]).astype(jnp.int32)
        return (state, a), a

    carry = (_initial_carry(params, cell), jnp.int32(0))
    _, actions = jax.lax.scan(body, carry, xf)
    return actions


def plan_logp(params, feats, actions, *, cell: str, num_types: int, mask=None):
    """Teacher-forced Σ_l log P(a_l | a_{(l-1):1}; θ) (Formula 14).

    Padded rows (``mask`` False) are zero-weighted out of the sum.  Uses
    the same hoisted input projection as :func:`sample_plan`, so the two
    produce bit-identical log-probs for the same action sequence.
    """
    step = _lstm_step if cell == "lstm" else _rnn_step
    xf, wx_a = _input_proj(params, feats)

    def body(carry, inp):
        state, prev_a = carry
        zf, a, m = inp
        state, h = step(params, state, zf + wx_a[prev_a])
        logits = h @ params["wo"] + params["bo"]
        return (state, a), jax.nn.log_softmax(logits)[a] * m

    carry = (_initial_carry(params, cell), jnp.int32(0))
    _, logps = jax.lax.scan(
        body, carry, (xf, actions, _step_mask(feats, mask))
    )
    return logps.sum()


@partial(jax.jit, static_argnames=("cell", "num_types"))
def sample_batch(params, feats, keys, *, cell: str, num_types: int,
                 temperature=1.0, mask=None):
    return jax.vmap(
        lambda k: sample_plan(
            params, feats, k, cell=cell, num_types=num_types,
            temperature=temperature, mask=mask,
        )
    )(keys)


@partial(jax.jit, static_argnames=("cell", "num_types"))
def reinforce_grad(params, feats, actions_batch, advantages, *, cell,
                   num_types, mask=None):
    """∇θ of the REINFORCE surrogate (Formula 15): mean over the batch of
    ``advantage · log P(plan)`` — gradient *ascent* direction on reward."""

    def surrogate(p):
        logps = jax.vmap(
            lambda a: plan_logp(p, feats, a, cell=cell, num_types=num_types,
                                mask=mask)
        )(actions_batch)
        return jnp.mean(advantages * logps)

    return jax.grad(surrogate)(params)
