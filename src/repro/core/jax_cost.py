"""JAX-native soft cost model — Formulas 1–7 + graded surrogate, on device.

This is the fused RL search's reward function: a pure-``jnp`` port of the
NumPy batched path (``plan.batched_build_stages`` →
``provision.batched_provision`` → ``cost_model.batched_soft_plan_cost``)
that can be traced into a single jitted program together with policy
sampling and the REINFORCE update (see ``schedulers/rl.py``).  The NumPy
implementation remains the reference oracle; equivalence over randomized
plans/fleets/jobs is pinned in ``tests/test_jax_cost.py``.

Design constraints that shape the port:

* **Static shapes.** Stage counts vary per plan, so every per-stage array
  is padded to ``S = L`` (a plan can have at most one stage per layer)
  with a validity mask, instead of NumPy's per-batch ``max(num_stages)``.
* **Layer padding.** All tensors carry a per-layer validity mask so
  several models can be padded to a common ``L_max`` and the whole search
  ``vmap``-ed across them (``RLScheduler.schedule_many``).  Padded layers
  contribute nothing: no stage boundaries, zero OCT/ODT.
* **No early exits.** NumPy's Newton loop retires converged plans and the
  graded surrogate re-provisions only the infeasible subset; under ``jit``
  we run fixed-trip loops with masked updates and compute the relaxed
  provisioning for every plan, selecting with ``where`` — same results,
  branch-free.
* **Precision.** All arrays are built from float64 NumPy inputs and take
  whatever precision JAX canonicalizes to: float64 under
  ``jax.enable_x64(True)`` (the fused scheduler runs its cost
  side there — agreement with the oracle is then ~1e-9 relative), float32
  otherwise (agreement to ~1e-3 on log-cost; documented in DESIGN.md).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import TrainingJob
from repro.core.profiles import B_O, LayerProfile
from repro.core.resources import ResourceType

#: fixed trip count of the Newton iteration — matches the NumPy default
NEWTON_ITERS = 25


class CostTensors(NamedTuple):
    """Device-resident constants for one job: per-layer profile tables,
    fleet prices/limits, and job scalars.  A NamedTuple so it is a pytree:
    close over it in a jitted search, pass it through ``lax.scan``, or
    stack ``M`` of them and ``vmap`` across models."""

    oct: jax.Array        # (L, T) per-layer OCT per resource type
    sync: jax.Array       # (L, T) per-layer gradient/param sync ODT
    act: jax.Array        # (L, T) per-layer activation hand-off ODT
    alpha: jax.Array      # (L,) Amdahl compute fraction
    beta: jax.Array       # (L,) Amdahl comm fraction
    lmask: jax.Array      # (L,) bool — False on padded layer slots
    price: jax.Array      # (T,) price per second
    maxc: jax.Array       # (T,) per-type unit limits (Formula 10)
    batch: jax.Array      # () global batch size B
    et_num: jax.Array     # () num_epochs * num_examples
    tau_limit: jax.Array  # () throughput_limit (Formula 10)

    @property
    def num_layers_padded(self) -> int:
        return self.oct.shape[0]

    @property
    def num_types(self) -> int:
        return self.oct.shape[1]


def cost_tensors(
    profiles: Sequence[LayerProfile],
    fleet: Sequence[ResourceType],
    job: TrainingJob,
    *,
    pad_to: int | None = None,
) -> CostTensors:
    """Build :class:`CostTensors`, optionally padding the layer axis.

    Arrays are assembled in float64 NumPy and handed to JAX's dtype
    canonicalization (float64 iff x64 is enabled at call time).
    """
    L = len(profiles)
    P = pad_to if pad_to is not None else L
    if P < L:
        raise ValueError(f"pad_to={P} < {L} layers")
    T = len(fleet)

    def lay(get):
        a = np.zeros((P, T))
        for i, p in enumerate(profiles):
            a[i] = get(p)
        return a

    alpha = np.zeros(P)
    beta = np.zeros(P)
    for i, p in enumerate(profiles):
        alpha[i], beta[i] = p.alpha, p.beta
    return CostTensors(
        oct=jnp.asarray(lay(lambda p: p.oct)),
        sync=jnp.asarray(lay(lambda p: p.odt_sync)),
        act=jnp.asarray(lay(lambda p: p.odt_act)),
        alpha=jnp.asarray(alpha),
        beta=jnp.asarray(beta),
        lmask=jnp.asarray(np.arange(P) < L),
        price=jnp.asarray(np.array([r.price_per_sec for r in fleet])),
        maxc=jnp.asarray(np.array([float(r.max_count) for r in fleet])),
        batch=jnp.asarray(float(job.batch_size)),
        et_num=jnp.asarray(float(job.num_epochs * job.num_examples)),
        tau_limit=jnp.asarray(float(job.throughput_limit)),
    )


class _Stages(NamedTuple):
    """Per-stage arrays for N plans, padded to S = L (cf. plan.StageBatch)."""

    rtype: jax.Array   # (N, S) int resource type (0 in invalid slots)
    oct: jax.Array     # (N, S)
    odt: jax.Array     # (N, S)
    alpha: jax.Array   # (N, S)
    beta: jax.Array    # (N, S)
    mask: jax.Array    # (N, S) bool


def build_stages(ct: CostTensors, actions: jax.Array) -> _Stages:
    """Fuse consecutive same-type layers into stages (plan.build_stages).

    ``actions`` is ``(N, L)`` int; padded layer slots (``ct.lmask`` False)
    never open a stage and contribute zero OCT/ODT.
    """
    N, L = actions.shape
    lm = ct.lmask
    lmf = lm.astype(ct.oct.dtype)
    n_layers = jnp.sum(lm)

    lay = jnp.arange(L)
    oct_l = ct.oct[lay, actions] * lmf          # (N, L)
    sync_l = ct.sync[lay, actions] * lmf
    act_l = ct.act[lay, actions] * lmf

    change = jnp.concatenate(
        [jnp.ones((N, 1), bool), actions[:, 1:] != actions[:, :-1]], axis=1
    ) & lm
    sid = jnp.cumsum(change, axis=1) - 1        # (N, L) stage id per layer
    # last layer of a stage: the next layer opens a new stage, or it is the
    # last *valid* layer (padded slots have change=False, so the real last
    # layer needs the explicit test)
    nxt = jnp.concatenate([change[:, 1:], jnp.zeros((N, 1), bool)], axis=1)
    is_last = (nxt | (lay[None, :] == n_layers - 1)) & lm

    onehot = (sid[:, :, None] == jnp.arange(L)[None, None, :]).astype(
        ct.oct.dtype
    )                                           # (N, L, S)

    def seg(v):
        return jnp.einsum("nl,nls->ns", v, onehot)

    oct_s = seg(oct_l)
    odt_s = seg(sync_l) + seg(jnp.where(is_last, act_l, 0.0))
    w = jnp.maximum(oct_s, 1e-30)
    alpha_s = seg(ct.alpha[None, :] * oct_l) / w
    beta_s = seg(ct.beta[None, :] * oct_l) / w
    # the stage's type is its first layer's action (change marks exactly one
    # layer per stage)
    rtype = jnp.einsum(
        "nl,nls->ns", actions * change, onehot.astype(actions.dtype)
    )
    smask = jnp.arange(L)[None, :] < (sid[:, -1] + 1)[:, None]
    return _Stages(
        rtype=rtype, oct=oct_s, odt=odt_s, alpha=alpha_s, beta=beta_s,
        mask=smask,
    )


def _required_k(st: _Stages, tau: jax.Array) -> jax.Array:
    """Vectorized ``provision.required_k``: (N, S) continuous k at per-plan
    target throughput ``tau`` (inf past a stage's Amdahl ceiling)."""
    budget = 1.0 / tau[:, None]
    out = jnp.full_like(st.oct, 1.0)
    for time_per_ex, frac in (
        (st.oct / B_O, st.alpha), (st.odt / B_O, st.beta)
    ):
        slack = budget / time_per_ex - (1.0 - frac)
        k = jnp.where(slack > 0.0, frac / slack, jnp.inf)
        k = jnp.where(time_per_ex <= 0.0, 0.0, k)
        out = jnp.maximum(out, k)
    return out


def _cost_at_tau(
    ct: CostTensors, st: _Stages, tau: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Continuous-relaxation cost at per-plan ``tau`` → (cost (N,), ks (N, S)).

    inf where a stage hits its Amdahl ceiling.  ``cumsum``-based folds
    mirror the NumPy path's left-to-right stage accumulation.
    """
    ks = _required_k(st, tau)
    ksm = jnp.where(st.mask, ks, 0.0)
    ok = jnp.all(jnp.isfinite(ksm), axis=1)
    stage_price = jnp.where(st.mask, ct.price[st.rtype], 0.0)
    accel_ind = jnp.where(st.mask & (st.rtype != 0), 1.0, 0.0)
    rate = jnp.cumsum(ksm * stage_price, axis=1)[:, -1]
    accel = jnp.cumsum(ksm * accel_ind, axis=1)[:, -1]
    ps = jnp.where(accel > 0.0, jnp.ceil(accel / 6.0), 0.0)
    rate = rate + ps * ct.price[0]
    cost = jnp.where(ok, (ct.et_num / tau) * rate, jnp.inf)
    return cost, ksm


def _int_throughput(
    ct: CostTensors, st: _Stages, k: jax.Array
) -> jax.Array:
    """Pipeline throughput (Formula 5) under integer replica counts."""
    k_eff = jnp.maximum(k, 1).astype(st.oct.dtype)
    cts = (st.oct / B_O) * ct.batch * (1.0 - st.alpha + st.alpha / k_eff)
    dts = (st.odt / B_O) * ct.batch * (1.0 - st.beta + st.beta / k_eff)
    ex = jnp.maximum(cts, dts)
    tp_s = jnp.where(
        st.mask & (ex > 0.0),
        ct.batch / jnp.where(ex > 0.0, ex, 1.0),
        jnp.inf,
    )
    return jnp.min(tp_s, axis=1)


def _type_counts(
    ct: CostTensors, st: _Stages, k: jax.Array, ps: jax.Array
) -> jax.Array:
    """(N, T) total units per resource type, PS cores on type 0."""
    onehot_t = (
        st.rtype[:, :, None] == jnp.arange(ct.num_types)[None, None, :]
    ).astype(k.dtype)
    counts = jnp.einsum("ns,nst->nt", k, onehot_t)
    return counts.at[:, 0].add(ps)


class _Provisioning(NamedTuple):
    k: jax.Array         # (N, S) integer replica counts (0 in invalid slots)
    ps: jax.Array        # (N,) PS cores
    feasible: jax.Array  # (N,) bool


def provision(
    ct: CostTensors, st: _Stages, tau_min: jax.Array
) -> _Provisioning:
    """Vectorized ``provision.batched_provision``: Newton on the throughput
    target τ (fixed ``NEWTON_ITERS`` trips, masked updates), integer
    rounding, Formula-10 limit + throughput checks."""
    c0, _ = _cost_at_tau(ct, st, tau_min)
    alive = jnp.isfinite(c0)
    h = jnp.maximum(tau_min * 1e-4, 1e-9)

    def body(_, carry):
        tau, best_tau, best_cost, cc, active = carry
        cm, _ = _cost_at_tau(ct, st, jnp.maximum(tau - h, tau_min))
        cp, _ = _cost_at_tau(ct, st, tau + h)
        active = active & jnp.isfinite(cm) & jnp.isfinite(cp) & jnp.isfinite(cc)
        g = (cp - cm) / (2 * h)
        hess = (cp - 2 * cc + cm) / (h * h)
        step = jnp.where(
            (hess <= 0.0) | ~jnp.isfinite(hess),
            # copysign(0.1·τ, g) for every g an active lane can hold (a
            # difference of positive costs is never -0.0); the TPU's x64
            # rewrite has no f64 sign-bit bitcast, which copysign needs
            jnp.where(g < 0.0, 0.1 * tau, -0.1 * tau),
            -g / hess,
        )
        new_tau = jnp.where(active, jnp.maximum(tau_min, tau + step), tau)
        c_new, _ = _cost_at_tau(ct, st, new_tau)
        better = active & jnp.isfinite(c_new) & (c_new < best_cost)
        best_cost = jnp.where(better, c_new, best_cost)
        best_tau = jnp.where(better, new_tau, best_tau)
        active = active & ~(jnp.abs(new_tau - tau) < 1e-6 * tau_min)
        return new_tau, best_tau, best_cost, c_new, active

    _, best_tau, _, _, _ = jax.lax.fori_loop(
        0, NEWTON_ITERS, body, (tau_min, tau_min, c0, c0, alive)
    )
    _, ks = _cost_at_tau(ct, st, best_tau)
    k_int = jnp.where(
        alive[:, None] & st.mask,
        jnp.ceil(jnp.where(alive[:, None], ks, 0.0)),
        0.0,
    )
    accel = jnp.sum(jnp.where(st.rtype != 0, k_int, 0.0), axis=1)
    ps = jnp.where(accel > 0.0, jnp.ceil(accel / 6.0), 0.0)
    counts = _type_counts(ct, st, k_int, ps)
    limit_ok = jnp.all(counts <= ct.maxc[None, :], axis=1)
    tp = _int_throughput(ct, st, k_int)
    return _Provisioning(
        k=k_int, ps=ps, feasible=alive & limit_ok & (tp >= tau_min)
    )


def _monetary(
    ct: CostTensors, st: _Stages, k: jax.Array, ps: jax.Array
) -> jax.Array:
    """Formulas 5–7 for integer provisioning, no constraint checks."""
    tp = _int_throughput(ct, st, k)
    et = ct.et_num / tp
    counts = _type_counts(ct, st, k, ps)
    rate = jnp.cumsum(counts * ct.price[None, :], axis=1)[:, -1]
    return et * rate


class SoftCost(NamedTuple):
    """Per-plan results of :func:`soft_cost` — the device analogue of
    ``(batched_plan_cost.costs, soft)`` plus the feasibility mask that lets
    the host reconstruct exact true costs (feasible ⇒ cost == soft;
    infeasible ⇒ cost == inf)."""

    soft: jax.Array      # (N,) graded surrogate (finite unless degenerate)
    cost: jax.Array      # (N,) true cost, inf where infeasible
    feasible: jax.Array  # (N,) bool


def soft_cost(ct: CostTensors, actions: jax.Array) -> SoftCost:
    """Vectorized ``cost_model.batched_soft_plan_cost`` in pure jnp.

    Unlike the NumPy path, the relaxed re-provisioning runs for every plan
    (no dynamic subsetting under jit) and ``where`` selects; feasible
    plans' relaxed branch is computed-and-discarded.
    """
    st = build_stages(ct, actions)
    bp = provision(ct, st, jnp.broadcast_to(ct.tau_limit, actions.shape[:1]))
    cost = jnp.where(bp.feasible, _monetary(ct, st, bp.k, bp.ps), jnp.inf)

    # graded surrogate for the infeasible subset: max achievable pipeline
    # throughput with every stage at its type's limit, re-provision at a
    # relaxed target, scale by squared constraint violation
    k_cap = jnp.where(st.mask, ct.maxc[st.rtype], 0.0)
    tp_max = _int_throughput(ct, st, k_cap)
    relaxed = jnp.minimum(tp_max * 0.5, ct.tau_limit)
    bp_r = provision(ct, st, relaxed)
    base = _monetary(ct, st, bp_r.k, bp_r.ps)
    violation = jnp.maximum(ct.tau_limit / jnp.maximum(tp_max, 1e-9), 1.0)
    graded = base * 10.0 * violation**2
    soft_infeas = jnp.where(bp_r.feasible & (tp_max > 0), graded, 1e15)
    return SoftCost(
        soft=jnp.where(bp.feasible, cost, soft_infeas),
        cost=cost,
        feasible=bp.feasible,
    )


@jax.jit
def _soft_cost_jit(ct: CostTensors, actions: jax.Array) -> SoftCost:
    return soft_cost(ct, actions)


def jnp_soft_plan_cost(
    assignments: np.ndarray,
    profiles: Sequence[LayerProfile],
    fleet: Sequence[ResourceType],
    job: TrainingJob,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-convenience wrapper: (soft, cost, feasible) NumPy arrays for an
    (N, L) assignment batch — the equivalence-test entry point."""
    ct = cost_tensors(profiles, fleet, job)
    out = _soft_cost_jit(ct, jnp.asarray(np.asarray(assignments), jnp.int32))
    return (
        np.asarray(out.soft, dtype=np.float64),
        np.asarray(out.cost, dtype=np.float64),
        np.asarray(out.feasible),
    )
