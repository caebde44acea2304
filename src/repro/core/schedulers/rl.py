"""Reinforcement-learning scheduler (HeterPS §5.2, Algorithm 1).

REINFORCE (Williams) over the LSTM policy of ``policy.py``:

* each round samples ``N`` scheduling plans from the current policy;
* each plan's reward is the (negated, log-scaled) monetary cost from the
  cost model, with the provisioning module invoked inside the evaluation
  (Algorithm 1 Line 5 — ``R_n ← Cost(SP)``);
* a moving-average baseline ``b ← (1-γ)·b + γ/N·ΣR_n`` reduces variance
  (Formula 15, Line 8);
* parameters update by gradient ascent (Formula 16) — we use Adam rather
  than plain SGD for round-count economy (noted deviation; plain SGD is
  available via ``optimizer="sgd"``).

Two implementations of the search loop:

* **fused** (default): sample → soft-cost reward (``jax_cost``) →
  baseline/advantage → ``reinforce_grad`` → optimizer step is ONE jitted
  program, ``lax.scan``-ned over chunks of rounds; the host only harvests
  per-round history, back-fills the :class:`CostCache` memo
  (``seed_from_device``) and checks early stopping *between* chunks.
  ``schedule_many`` additionally ``vmap``s the whole search across several
  models (layer features padded to a common length, see DESIGN.md).
  Runs its cost side under ``jax.enable_x64(True)`` so rewards
  agree with the NumPy oracle to ~1e-9 while policy math stays float32.
* **unfused** (``fused=False``): the original per-round Python loop — one
  device round-trip per round, NumPy ``batched_soft_plan_cost`` scoring.
  Kept as the oracle the fused path is equivalence-tested against and as
  the baseline for the ``bench_table3`` speedup gate.
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jax_cost
from repro.core.cost_model import plan_cost
from repro.core.plan import SchedulingPlan
from repro.core.schedulers import policy as pol
from repro.core.schedulers.base import CostCache, ScheduleResult, Scheduler


def _adam_update(params, grads, state, lr, b1=0.9, b2=0.999, eps=1e-8):
    m, v, t = state
    t = t + 1  # python int in the unfused loop, traced int32 in the scan
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    # float32-explicit bias corrections: identical math whether t is a
    # python int or a traced scalar, and no f64 promotion under x64
    c1 = 1.0 - jnp.float32(b1) ** t
    c2 = 1.0 - jnp.float32(b2) ** t
    mh = jax.tree.map(lambda a: a / c1, m)
    vh = jax.tree.map(lambda a: a / c2, v)
    # ASCENT: reward gradients point uphill
    new = jax.tree.map(lambda p, a, b: p + lr * a / (jnp.sqrt(b) + eps), params, mh, vh)
    return new, (m, v, t)


# --- fused search kernel -----------------------------------------------------

_STATIC = ("cell", "num_types", "optimizer", "plans", "early_stop")


@partial(jax.jit, static_argnames=("c",))
def _round_keys(key, c: int):
    """The unfused loop's per-round key stream, batched: replays
    ``key, ks = split(key)`` ``c`` times in one dispatch."""

    def body(k, _):
        k, ks = jax.random.split(k)
        return k, ks

    return jax.lax.scan(body, key, None, length=c)


def _chunk_scan(carry, rks, feats, mask, ct, lr, gamma, temperature,
                *, cell, num_types, optimizer, plans, early_stop):
    """``C = len(rks)`` fused REINFORCE rounds for one model.

    One round = sample ``plans`` plans → soft cost on device → advantage →
    REINFORCE gradient → optimizer step.  Stacks per-round (params,
    actions, soft, feasible, stop) so the host can harvest history and
    back-fill the cost cache; the early-stop bookkeeping (best cost /
    rounds-since-improvement) lives in the scan carry, so ``stop`` is a
    device-computed flag the host only *reads* between chunks — once
    every member of a vmapped group has flagged, the remaining chunks
    are skipped entirely.
    """

    def body(c, _ks):
        params, opt, b, binit, best, since = c
        keys = jax.random.split(_ks, plans)

        # one forward pass both samples the plans and records the vjp of
        # their (untempered) log-probs — the REINFORCE gradient is then a
        # single backward with the advantages as cotangent, with no
        # teacher-forced re-evaluation (Formula 15: ∇ mean(adv · log P))
        def fwd(p):
            actions, logps = pol.sample_batch(
                p, feats, keys, cell=cell, num_types=num_types,
                temperature=temperature, mask=mask,
            )
            return logps, actions

        logps, vjp_fn, actions = jax.vjp(fwd, params, has_aux=True)
        sc = jax_cost.soft_cost(ct, actions)
        rewards = -jnp.log10(sc.soft + 1e-12)
        rmean = jnp.mean(rewards)
        b = jnp.where(binit, b, rmean)              # Line 1: b ← first mean
        binit = jnp.ones_like(binit)
        adv = (rewards - b).astype(jnp.float32)
        (grads,) = vjp_fn(adv / plans)              # d mean(adv·logp) / dθ
        if optimizer == "adam":
            params, opt = _adam_update(params, grads, opt, lr)
        else:
            params = jax.tree.map(lambda p, g: p + lr * g, params, grads)
        b = (1 - gamma) * b + gamma * rmean         # Line 8
        # early-stop counter on device (same math the host loop used to
        # replay: strict improvement beyond 1e-12 resets the clock)
        round_best = jnp.min(sc.soft)
        improved = round_best < best - 1e-12
        since = jnp.where(improved, 0, since + 1)
        best = jnp.where(improved, round_best, best)
        stop = since >= early_stop
        return (params, opt, b, binit, best, since), (
            params, actions, sc.soft, sc.feasible, stop)

    return jax.lax.scan(body, carry, rks)


_chunk_single = partial(jax.jit, static_argnames=_STATIC)(_chunk_scan)


@partial(jax.jit, static_argnames=_STATIC)
def _chunk_multi(carry, rks, feats, mask, ct, lr, gamma, temperature,
                 *, cell, num_types, optimizer, plans, early_stop):
    """vmap of :func:`_chunk_scan` across models; the round-key stream is
    shared (each model sees the same keys a solo run with this seed would)."""
    f = partial(_chunk_scan, cell=cell, num_types=num_types,
                optimizer=optimizer, plans=plans, early_stop=early_stop)
    return jax.vmap(f, in_axes=(0, None, 0, 0, 0, None, None, None))(
        carry, rks, feats, mask, ct, lr, gamma, temperature
    )


class RLScheduler(Scheduler):
    """``cell="lstm"`` is HeterPS; ``cell="rnn"`` is the RL-RNN baseline."""

    def __init__(
        self,
        cell: str = "lstm",
        hidden: int = 64,
        rounds: int = 150,
        plans_per_round: int = 32,
        lr: float = 0.03,
        gamma: float = 0.3,
        temperature: float = 2.0,
        optimizer: str = "adam",
        seed: int = 0,
        early_stop_rounds: int = 50,
        fused: bool = True,
        chunk_rounds: int = 25,
    ):
        assert cell in ("lstm", "rnn")
        self.cell = cell
        self.name = "RL-LSTM" if cell == "lstm" else "RL-RNN"
        self.hidden = hidden
        self.rounds = rounds
        self.plans_per_round = plans_per_round
        self.lr = lr
        self.gamma = gamma
        self.temperature = temperature
        self.optimizer = optimizer
        self.seed = seed
        self.early_stop_rounds = early_stop_rounds
        self.fused = fused
        self.chunk_rounds = chunk_rounds

    # -- shared pieces --------------------------------------------------------

    def _anchored_cache(self, profiles, fleet, job, warm=()) -> CostCache:
        """Cache pre-seeded with the warm-start anchors (beyond-paper,
        DESIGN.md): the homogeneous plans (Algorithm 1 "may also generate
        a homogeneous scheduling plan") and the AIBox heuristic
        (data-intensive layers → type 0).  ``warm`` adds caller-supplied
        assignment vectors — e.g. the re-planner's incumbent plan — to the
        anchor set (malformed entries are ignored).  Anchors are
        oracle-scored here and the final plan is best-of(search ∪
        anchors), so RL never returns worse than the static heuristics it
        subsumes, nor worse than any warm start it was seeded with."""
        T, L = len(fleet), len(profiles)
        cache = CostCache(profiles, fleet, job)
        anchors = [(t,) * L for t in range(T)]
        if T > 1:
            anchors.append(tuple(
                0 if p.kind in ("embedding", "nce") else 1 for p in profiles
            ))
        for w in warm:
            a = tuple(int(x) for x in w)
            if len(a) == L and all(0 <= x < T for x in a):
                anchors.append(a)
        cache.batch_call(anchors)
        return cache

    def _select_plan(self, cache, params, feats, num_layers, T):
        """Final decision: argmax decode (§5.2) — but never return
        something worse than the best plan seen during the search.

        The winner is re-verified against the NumPy oracle before being
        returned: fused-search memo entries are device-scored, and on an
        exact constraint boundary f64 op-reordering can flip feasibility
        between XLA and NumPy.  A disagreement pins the oracle verdict
        into the cache and re-selects, so the anchor guarantee (anchors
        are always oracle-scored) survives.
        """

        ga = pol.greedy_plan(params, feats, cell=self.cell, num_types=T)
        greedy = tuple(int(a) for a in np.asarray(ga)[:num_layers])
        greedy_cost = cache(greedy)
        while True:
            best_seen, best_seen_cost = cache.best()
            plan = greedy if greedy_cost <= best_seen_cost else best_seen
            if not cache.device_seeded:
                break  # every entry is oracle-written: nothing to verify
            oracle_cost, _ = plan_cost(
                SchedulingPlan(plan), cache.profiles, cache.fleet, cache.job
            )
            if math.isfinite(oracle_cost) or not math.isfinite(
                min(greedy_cost, best_seen_cost)
            ):
                break  # oracle agrees, or nothing feasible exists anyway
            cache.pin_true(plan, oracle_cost)
            if plan == greedy:
                greedy_cost = oracle_cost
        return plan, greedy_cost

    # -- search entry points --------------------------------------------------

    def _search(self, profiles, fleet, job):
        if self.fused:
            return self._fused_search([(profiles, fleet, job)])[0]
        return self._search_unfused(profiles, fleet, job)

    def schedule_many(
        self, specs: Sequence[tuple], warm_starts: Sequence | None = None
    ) -> list[ScheduleResult]:
        """Schedule several ``(profiles, fleet, job)`` workloads in one
        vmapped fused search per fleet-size group.

        Models are grouped by resource-type count (vmap needs uniform
        tensor shapes; padding the *type* axis would distort sampling),
        layer features are padded to the group's max layer count with a
        mask, and the entire chunked search runs as one program per group.
        Per-model results are identical in structure to ``schedule()``'s.
        With ``fused=False`` this degrades to a sequential loop.

        ``warm_starts[i]``, when given, is a sequence of assignment
        vectors seeded as oracle-scored anchors for ``specs[i]`` — the
        reactive re-planner passes its incumbent plan here, so the search
        result is structurally never worse than the plan it might replace.
        """

        warms = ([() for _ in specs] if warm_starts is None
                 else [tuple(w) if w else () for w in warm_starts])
        assert len(warms) == len(specs)
        results: dict[int, ScheduleResult] = {}
        if not self.fused:
            for i, (p, f, j) in enumerate(specs):
                t0 = time.perf_counter()
                plan, evals, extra = self._search_unfused(
                    p, f, j, warm=warms[i])
                wall = time.perf_counter() - t0
                cost, prov = plan_cost(plan, p, f, j)
                results[i] = ScheduleResult(
                    plan=plan, prov=prov, cost=cost, wall_time_s=wall,
                    evaluations=evals, extra=extra,
                )
            return [results[i] for i in range(len(specs))]
        groups: dict[int, list[int]] = {}
        for i, (_, fleet, _) in enumerate(specs):
            groups.setdefault(len(fleet), []).append(i)
        for idxs in groups.values():
            t0 = time.perf_counter()
            outs = self._fused_search([specs[i] for i in idxs],
                                      warm_starts=[warms[i] for i in idxs])
            wall = time.perf_counter() - t0
            for i, (plan, evals, extra) in zip(idxs, outs):
                profiles, fleet, job = specs[i]
                cost, prov = plan_cost(plan, profiles, fleet, job)
                results[i] = ScheduleResult(
                    plan=plan, prov=prov, cost=cost, wall_time_s=wall,
                    evaluations=evals, extra=extra,
                )
        return [results[i] for i in range(len(specs))]

    # -- fused implementation -------------------------------------------------

    def _fused_search(self, specs, warm_starts=None):
        """Chunked-scan REINFORCE for one or more same-fleet-size models.

        Returns ``[(plan, evaluations, extra), ...]`` aligned with
        ``specs``.  See the module docstring and DESIGN.md for the
        host/device split.
        """
        M = len(specs)
        T = len(specs[0][1])
        assert all(len(f) == T for _, f, _ in specs), "group by fleet size"
        Lmax = max(len(p) for p, _, _ in specs)
        num_layers = [len(p) for p, _, _ in specs]
        warms = warm_starts if warm_starts is not None else [()] * M
        caches = [self._anchored_cache(p, f, j, warm=w)
                  for (p, f, j), w in zip(specs, warms)]

        # policy init in float32, OUTSIDE the x64 context (matches unfused)
        key = jax.random.PRNGKey(self.seed)
        key, kinit = jax.random.split(key)
        fm = [pol.layer_features(p, pad_to=Lmax, return_mask=True)
              for p, _, _ in specs]
        feats_np = np.stack([f for f, _ in fm])
        mask_np = np.stack([m for _, m in fm])
        in_dim = feats_np.shape[2] + T
        init = pol.init_lstm if self.cell == "lstm" else pol.init_rnn
        params1 = init(kinit, in_dim, self.hidden, T)

        C = max(1, min(self.chunk_rounds, self.rounds))
        histories = [[] for _ in range(M)]
        stopped = [False] * M
        greedy_params = [None] * M  # per-model params at its final round
        chunk_times: list[float] = []

        with jax.enable_x64(True):
            feats = jnp.asarray(feats_np)   # float32 (explicit in builder)
            mask = jnp.asarray(mask_np)
            cts = [jax_cost.cost_tensors(p, f, j, pad_to=Lmax)
                   for p, f, j in specs]
            if M == 1:
                ct, feats_a, mask_a = cts[0], feats[0], mask[0]
                stack = lambda x: x  # noqa: E731
                chunk_fn = _chunk_single
            else:
                ct = jax.tree.map(lambda *xs: jnp.stack(xs), *cts)
                feats_a, mask_a = feats, mask
                stack = lambda x: jnp.stack([x] * M)  # noqa: E731
                chunk_fn = _chunk_multi
            params = jax.tree.map(stack, params1)
            opt_state = (
                jax.tree.map(jnp.zeros_like, params),
                jax.tree.map(jnp.zeros_like, params),
                stack(jnp.int32(0)),
            )
            b = stack(jnp.zeros(()))
            binit = stack(jnp.zeros((), bool))
            # device-side early-stop state: best soft cost so far + rounds
            # since the last improvement (the scan emits the stop flag)
            best = stack(jnp.full((), jnp.inf))
            since = stack(jnp.int32(0))
            carry = (params, opt_state, b, binit, best, since)

            rounds_done = 0
            # every chunk runs the full static length C — a shorter final
            # chunk would jit-compile a second program shape, which costs
            # far more than the <=C-1 discarded device rounds; callers
            # that care (bench_table3) pick chunk_rounds dividing rounds
            while rounds_done < self.rounds and not all(stopped):
                key, rks = _round_keys(key, C)
                t0 = time.perf_counter()
                carry, (pstack, acts, softs, feas, stops) = chunk_fn(
                    carry, rks, feats_a, mask_a, ct,
                    self.lr, self.gamma, self.temperature,
                    cell=self.cell, num_types=T, optimizer=self.optimizer,
                    plans=self.plans_per_round,
                    early_stop=self.early_stop_rounds,
                )
                jax.block_until_ready(softs)
                acts_h = np.asarray(acts)
                softs_h = np.asarray(softs)
                feas_h = np.asarray(feas)
                stops_h = np.asarray(stops)
                if M == 1:  # normalize to a leading model axis
                    acts_h, softs_h, feas_h, stops_h = (
                        acts_h[None], softs_h[None], feas_h[None],
                        stops_h[None])

                last_round = min(rounds_done + C, self.rounds) - 1
                for m in range(M):
                    if stopped[m]:
                        continue
                    final_c = last_round - rounds_done
                    for c in range(C):
                        r = rounds_done + c
                        if r >= self.rounds:
                            break
                        caches[m].seed_from_device(
                            acts_h[m, c, :, : num_layers[m]],
                            softs_h[m, c], feas_h[m, c],
                        )
                        histories[m].append(float(softs_h[m, c].min()))
                        # device-computed stop flag: once every group
                        # member has flagged, the while-loop skips the
                        # remaining chunks for this group entirely
                        if stops_h[m, c]:
                            stopped[m], final_c = True, c
                            break
                    # params after this model's final executed round — the
                    # exact parameters the unfused loop would greedy-decode
                    greedy_params[m] = jax.tree.map(
                        (lambda x, mm=m, cc=final_c: x[mm, cc]) if M > 1
                        else (lambda x, cc=final_c: x[cc]),
                        pstack,
                    )
                rounds_done += C
                # per-chunk time includes the host harvest above, so the
                # reported rounds_per_s is end-to-end, not device-only
                chunk_times.append(time.perf_counter() - t0)

        steady = chunk_times[1:]
        compile_s = max(0.0, chunk_times[0] - (min(steady) if steady else 0.0))
        rounds_per_s = (
            (len(steady) * C) / sum(steady) if sum(steady) > 0 else None
        )

        out = []
        for m in range(M):
            plan, greedy_cost = self._select_plan(
                caches[m], greedy_params[m], feats[m] if M > 1 else feats[0],
                num_layers[m], T,
            )

            out.append((
                SchedulingPlan(plan),
                caches[m].evaluations,
                {
                    "rounds": len(histories[m]),
                    "history": histories[m],
                    "greedy_cost": greedy_cost,
                    "fused": True,
                    "vmapped_models": M,
                    "compile_s": compile_s,
                    "rounds_per_s": rounds_per_s,
                },
            ))
        return out

    # -- unfused (per-round NumPy-scored) implementation ----------------------

    def _search_unfused(self, profiles, fleet, job, warm=()):
        T = len(fleet)
        feats = jnp.asarray(pol.layer_features(profiles))
        in_dim = feats.shape[1] + T
        key = jax.random.PRNGKey(self.seed)
        key, kinit = jax.random.split(key)
        init = pol.init_lstm if self.cell == "lstm" else pol.init_rnn
        params = init(kinit, in_dim, self.hidden, T)
        opt_state = (
            jax.tree.map(jnp.zeros_like, params),
            jax.tree.map(jnp.zeros_like, params),
            0,
        )

        cache = self._anchored_cache(profiles, fleet, job, warm=warm)
        b = 0.0  # moving-average baseline (Algorithm 1, Line 1)
        b_init = False
        best_cost, best_since = float("inf"), 0
        history = []

        t_loop = time.perf_counter()
        for rnd in range(self.rounds):
            key, ks = jax.random.split(key)
            keys = jax.random.split(ks, self.plans_per_round)
            actions, _ = pol.sample_batch(
                params, feats, keys, cell=self.cell, num_types=T,
                temperature=self.temperature,
            )
            actions = np.asarray(actions)
            # graded surrogate: infeasible plans get finite costs ordered
            # by violation — keeps the REINFORCE signal alive even when a
            # whole round samples infeasible plans (see soft_plan_cost);
            # the whole round is scored in one vectorized pass
            costs = cache.batch_soft(actions)
            # reward: negative log-cost — scale-free across models/fleets
            rewards = -np.log10(costs + 1e-12)

            if not b_init:
                b, b_init = float(rewards.mean()), True
            adv = jnp.asarray(rewards - b, dtype=jnp.float32)
            grads = pol.reinforce_grad(
                params, feats, jnp.asarray(actions), adv,
                cell=self.cell, num_types=T,
            )
            if self.optimizer == "adam":
                params, opt_state = _adam_update(params, grads, opt_state, self.lr)
            else:
                params = jax.tree.map(lambda p, g: p + self.lr * g, params, grads)
            # Line 8: moving-average baseline update
            b = (1 - self.gamma) * b + self.gamma * float(rewards.mean())

            round_best = float(np.min(costs))
            history.append(round_best)
            if round_best < best_cost - 1e-12:
                best_cost, best_since = round_best, 0
            else:
                best_since += 1
            if best_since >= self.early_stop_rounds:
                break
        t_loop = time.perf_counter() - t_loop

        plan, greedy_cost = self._select_plan(
            cache, params, feats, len(profiles), T
        )

        return (
            SchedulingPlan(plan),
            cache.evaluations,
            {"rounds": rnd + 1, "history": history, "greedy_cost": greedy_cost,
             "fused": False,
             # round-loop throughput only (no anchors/greedy/final eval),
             # directly comparable to the fused path's rounds_per_s
             "rounds_per_s": (rnd + 1) / t_loop if t_loop > 0 else None},
        )
