"""Smoke check that the HeterPS paths run on a TPU.

Runs, in one process and through the entry points a user calls:

* ``scheduler`` — one fused RL-LSTM search (``RLScheduler``) on the
  Table-3 CTRDNN case over ``default_fleet()``; the selected plan's cost,
  recomputed by the on-device x64 cost model, must equal the NumPy
  oracle's ``plan_cost``.
* ``ctr`` — CTR training over the elastic multi-process parameter server
  with the reactive re-planner armed, as
  ``train --sparse-ps --ps-transport multiproc --replan
  --replan-window-steps 5 --steps 40`` runs it.
* ``kernels`` — each Pallas kernel (paged decode, MoE dispatch/combine,
  embedding bag, flash attention) against its jnp reference.
* ``serve`` — ``serve`` and ``serve_continuous`` on llama3.2-1b at its
  published widths with the paged KV cache, and a few decode steps of
  the paged cache against the dense-layout oracle.

``--four-chips`` runs only ``pipeline``: the CTR tower's 4-stage GPipe
pipeline (``parallel/pipeline.py``) over four chips against the same
stages run one after another on one chip.

Each phase prints one ``[phase] {json}`` line with its wall and compile
seconds and its checks; the last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero before any phase.  Timings here
are smoke timings, not benchmark results.

  python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

#: serve-phase shapes: a few batched requests of ~128 prompt tokens
SERVE_BATCH, SERVE_PROMPT, SERVE_GEN = 4, 128, 32
#: decode steps compared against the dense-layout oracle
ORACLE_STEPS = 4
#: the CTR driver's pipelined tower (examples/heterps_ctr_pipeline.py)
TOWER_D, LAYERS_PER_STAGE, MICRO, MB = 256, 2, 8, 32


class _CompileClock:
    """Sums XLA backend compile seconds reported by JAX's monitoring."""

    def __init__(self):
        import jax

        from jax._src import dispatch

        self.total = 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, secs, **_):
            if name == event:
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


def _run_phase(name: str, fn, clock: _CompileClock, **kw) -> dict:
    t0, c0 = time.perf_counter(), clock.total
    checks = fn(**kw)
    line = {"phase": name, "wall_s": time.perf_counter() - t0,
            "compile_s": clock.total - c0, "checks": checks}
    print("[phase] " + json.dumps(line), flush=True)
    return checks


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _max_abs(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def phase_scheduler(*, rounds: int = 40) -> dict:
    """Fused RL search on the Table-3 CTRDNN case; the plan's on-device
    x64 cost must equal the NumPy oracle's."""
    import jax
    import numpy as np

    from repro.core import (TrainingJob, default_fleet, jax_cost,
                            paper_model_profiles, plan_cost)
    from repro.core.schedulers.rl import RLScheduler

    fleet, job = default_fleet(), TrainingJob()
    profiles = paper_model_profiles("CTRDNN", fleet)
    # the re-planner's own search budget (core/replan.ctr_replan_factory),
    # so the ctr phase reuses this compiled search
    sched = RLScheduler(rounds=rounds, plans_per_round=16,
                        early_stop_rounds=15, chunk_rounds=10, seed=0)
    t0 = time.perf_counter()
    res = sched.schedule(profiles, fleet, job)
    plan_s = time.perf_counter() - t0
    oracle, _ = plan_cost(res.plan, profiles, fleet, job)
    with jax.enable_x64(True):
        _, cost, feas = jax_cost.jnp_soft_plan_cost(
            np.asarray([res.plan.assignment]), profiles, fleet, job)
    dev_cost = float(cost[0])
    rel = abs(dev_cost - oracle) / abs(oracle)
    _require(bool(feas[0]) and math.isfinite(oracle), "plan infeasible")
    # the x64 equivalence tolerance of tests/test_jax_cost.py
    _require(rel <= 1e-9, f"device cost {dev_cost} != oracle {oracle}")
    return {"plan": list(res.plan.assignment), "oracle_cost": oracle,
            "device_cost": dev_cost, "rel_err": rel,
            "rounds": res.extra["rounds"],
            "smoke_time_to_plan_s": plan_s}


def phase_ctr(*, steps: int = 40, platform: str = "tpu") -> dict:
    """CTR training over the multi-process elastic PS with re-planning."""
    from repro.core.replan import ReplanConfig
    from repro.launch.train import train_sparse_ps

    out = train_sparse_ps(steps=steps, transport="multiproc",
                          replan=ReplanConfig(window_steps=5))
    losses = out["losses"]
    rep = out["replan"]
    _require(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    _require(all(math.isfinite(x) for x in losses), "non-finite loss")
    _require(out["tower_platforms"] == [platform],
             f"tower on {out['tower_platforms']}, not {platform}")
    _require(rep["calibrations"] + rep["considered"] >= 1,
             "no re-planning search completed")
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1], "tower_platforms": out["tower_platforms"],
            "replan_windows": rep["windows"],
            "replan_calibrations": rep["calibrations"],
            "replan_considered": rep["considered"]}


def phase_kernels(*, impl: str = "pallas", full: bool = True) -> dict:
    """Each Pallas kernel against its jnp reference (``impl="interpret"``
    runs the kernel bodies in the Pallas interpreter instead)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, paged_attention as paged_k, ref
    from repro.nn import moe as moe_mod

    key = jax.random.PRNGKey(0)
    k = lambda i: jax.random.fold_in(key, i)  # noqa: E731
    out = {}
    # MXU passes may round f32 operands; a wrong page, head, row or mask
    # moves these outputs by O(1)
    att_tol = 2e-3
    with jax.default_matmul_precision("highest"):
        # paged decode at llama3.2-1b widths
        B, KV, G, hd, ps, P = (8, 8, 4, 64, 16, 16) if full else (
            2, 2, 2, 32, 4, 3)
        N = 1 + B * P
        q = jax.random.normal(k(1), (B, KV, G, hd))
        kp = jax.random.normal(k(2), (N, ps, KV, hd))
        vp = jax.random.normal(k(3), (N, ps, KV, hd))
        table = jax.random.permutation(k(4), N - 1)[:B * P].reshape(B, P) + 1
        q_pos = jax.random.randint(k(5), (B,), 0, P * ps)
        got = ops.paged_attention_decode(q, kp, vp, table.astype(jnp.int32),
                                         q_pos, impl=impl)
        want = paged_k.paged_decode_gather(q, kp, vp, table.astype(jnp.int32),
                                           q_pos)
        out["paged_max_abs"] = _max_abs(got, want)
        _require(out["paged_max_abs"] <= att_tol, "paged decode mismatch")

        # MoE dispatch/combine at olmoe-1b-7b widths
        Gr, S, D, E, K = (2, 256, 2048, 64, 8) if full else (2, 12, 16, 4, 2)
        p = moe_mod.init_moe(k(6), D, 2 * D, E)
        x = jax.random.normal(k(7), (Gr, S, D))
        C = moe_mod.moe_capacity(S, E, K)
        _, gate, eid, pos, keep = moe_mod.moe_route(p["router"], x, top_k=K,
                                                    capacity=C)
        wk = keep.astype(jnp.float32)
        bufs = [ops.moe_dispatch(x, eid, pos, wk, num_experts=E, capacity=C,
                                 top_k=K, impl=i) for i in (impl, "slot")]
        out["dispatch_max_abs"] = _max_abs(*bufs)
        _require(out["dispatch_max_abs"] <= 1e-6, "MoE dispatch mismatch")
        w = (gate.reshape(Gr, S, K) * keep.reshape(Gr, S, K))
        sp = jnp.where(keep, pos, 0).reshape(Gr, S, K)
        ys = [ops.moe_combine(bufs[1], eid.reshape(Gr, S, K), sp, w, impl=i)
              for i in (impl, "slot")]
        out["combine_max_abs"] = _max_abs(*ys)
        _require(out["combine_max_abs"] <= 1e-5, "MoE combine mismatch")

        # embedding bag at the CTR widths (200k x 16, 26 slots)
        Nb, bag, V, dim = (256, 26, 200_000, 16) if full else (8, 3, 100, 16)
        ids = jax.random.randint(k(8), (Nb, bag), 0, V)
        tab = jax.random.normal(k(9), (V, dim))
        got = ops.embedding_bag(ids, tab, impl=impl)
        out["embedding_bag_max_abs"] = _max_abs(
            got, ref.embedding_bag_ref(ids, tab))
        _require(out["embedding_bag_max_abs"] <= 1e-4, "embedding bag mismatch")

        # flash attention at llama3.2-1b head width
        Bf, H, Sf, hdf = (1, 32, 1024, 64) if full else (1, 2, 128, 32)
        qf, kf, vf = (jax.random.normal(k(10 + i), (Bf, H, Sf, hdf))
                      for i in range(3))
        got = ops.flash_attention(qf, kf, vf, impl=impl)
        out["flash_max_abs"] = _max_abs(
            got, ref.flash_attention_ref(qf, kf, vf, causal=True))
        _require(out["flash_max_abs"] <= att_tol, "flash attention mismatch")
    return out


def phase_serve(*, reduced: bool = False, platform: str = "tpu",
                batch: int = SERVE_BATCH, prompt_len: int = SERVE_PROMPT,
                gen: int = SERVE_GEN) -> dict:
    """Paged serving of llama3.2-1b, then paged-vs-dense decode logits."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import serve, serve_continuous
    from repro.models import decoder as dec

    arch = "llama3.2-1b"
    cache_len = prompt_len + gen
    r = serve(arch, reduced=reduced, kv_impl="paged", batch=batch,
              prompt_len=prompt_len, gen=gen, cache_len=cache_len)
    _require(r["tokens_in_vocab"], "serve: token outside the vocabulary")
    _require(r["generated_shape"] == [batch, gen], "serve: wrong shape")
    rc = serve_continuous(arch, reduced=reduced)
    _require(rc["tokens_in_vocab"], "continuous: token outside vocabulary")
    _require(rc["pool_conserved"], "continuous: page pool not conserved")
    _require(rc["outcome_counts"]["completed"] == rc["requests"],
             f"continuous: outcomes {rc['outcome_counts']}")

    # paged cache vs the dense-layout oracle, same weights, same tokens
    cfg = get_config(arch, reduced=reduced)
    key = jax.random.PRNGKey(0)
    params = dec.init_model(cfg, key)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)
    feed = jax.random.randint(jax.random.fold_in(key, 1),
                              (ORACLE_STEPS, batch, 1), 0, cfg.vocab)
    logits, hlo = {}, {}
    with jax.default_matmul_precision("highest"):
        for kv in ("paged", "dense"):
            c = dataclasses.replace(cfg, kv_impl=kv)
            cache = dec.init_cache(c, batch, cache_len, dtype=jnp.float32,
                                   page_size=16)
            _, cache = jax.jit(lambda p, t, ca, c=c: dec.prefill(
                p, c, t, ca, compute_dtype=jnp.float32))(params, prompts,
                                                         cache)
            step = jax.jit(lambda p, t, ca, i, c=c: dec.decode_step(
                p, c, t, ca, i, compute_dtype=jnp.float32))
            compiled = step.lower(params, feed[0], cache,
                                  jnp.int32(prompt_len)).compile()
            hlo[kv] = compiled.as_text()
            outs = []
            for i in range(ORACLE_STEPS):
                lg, cache = compiled(params, feed[i], cache,
                                     jnp.int32(prompt_len + i))
                outs.append(np.asarray(lg[..., :cfg.vocab], np.float64))
            logits[kv] = np.stack(outs)
            del cache
    scale = float(np.abs(logits["dense"]).max())
    gap = _max_abs(logits["paged"], logits["dense"])
    # both layouts run f32 at highest matmul precision and differ only in
    # summation order (page-by-page online softmax vs one softmax): the
    # gap is ~1e-5 relative; a wrong page, head or position mapping moves
    # logits by O(scale)
    tol = 1e-3 * max(scale, 1.0)
    _require(bool(np.isfinite(logits["paged"]).all()), "non-finite logits")
    _require(gap <= tol, f"paged logits off the dense oracle by {gap}")
    kernel_in_decode = "tpu_custom_call" in hlo["paged"]
    if platform == "tpu":
        _require(kernel_in_decode, "paged decode did not compile the kernel")
    return {"serve_decode_tok_per_s_smoke": r["decode_tok_per_s"],
            "continuous_requests": rc["requests"],
            "continuous_prefills": rc["prefills"],
            "pool_conserved": rc["pool_conserved"],
            "paged_decode_has_tpu_custom_call": kernel_in_decode,
            "oracle_steps": ORACLE_STEPS, "logit_scale": scale,
            "paged_vs_dense_max_abs": gap, "tolerance": tol}


def phase_pipeline(*, num_stages: int = 4) -> dict:
    """The CTR tower's GPipe stages over ``num_stages`` devices vs the
    same stages run one after another on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel.pipeline import (make_stage_mesh, pipeline_loss,
                                         stack_stage_params)

    devices = jax.devices()
    _require(len(devices) >= num_stages,
             f"{num_stages} stages need {num_stages} devices")
    key = jax.random.PRNGKey(0)
    k = lambda i: jax.random.fold_in(key, i)  # noqa: E731
    stages = [{"layers": [
        {"w": jax.random.normal(k(10 * s + l), (TOWER_D, TOWER_D))
         * TOWER_D ** -0.5, "b": jnp.zeros((TOWER_D,))}
        for l in range(LAYERS_PER_STAGE)]} for s in range(num_stages)]
    head_w = jax.random.normal(k(1), (TOWER_D,)) * TOWER_D ** -0.5
    xs = jax.random.normal(k(2), (MICRO, MB, TOWER_D))
    labels = (jax.random.uniform(k(3), (MICRO, MB)) > 0.5).astype(jnp.float32)

    def stage_fn(p, x):
        for layer in p["layers"]:
            x = x + jnp.tanh(x @ layer["w"] + layer["b"])
        return x

    def head_loss(h, y):
        logit = h @ head_w
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    mesh = make_stage_mesh(num_stages)
    stacked = stack_stage_params(stages)
    sp = jax.device_put(stacked, NamedSharding(mesh, P("stage")))
    # each stage's slice on its own device, in mesh order
    for leaf in jax.tree.leaves(sp):
        where = {s.index[0].start: s.device for s in leaf.addressable_shards}
        _require(len(set(where.values())) == num_stages,
                 "stage parameters share a device")
        _require(all(where[i] == d for i, d in
                     enumerate(mesh.devices.ravel())),
                 "stage parameters out of mesh order")

    def seq_loss(prm):
        h = xs
        for i in range(num_stages):
            p = jax.tree.map(lambda a, i=i: a[i], prm)
            h = jax.vmap(lambda x, p=p: stage_fn(p, x))(h)
        return jax.vmap(head_loss)(h, labels).mean()

    with jax.default_matmul_precision("highest"):
        pipe = jax.jit(jax.value_and_grad(lambda prm: pipeline_loss(
            stage_fn, head_loss, prm, xs, labels, mesh)))
        compiled = pipe.lower(sp).compile()
        hops = compiled.as_text().count("collective-permute")
        loss, grads = compiled(sp)
        one = jax.device_put(stacked, devices[0])
        loss1, grads1 = jax.jit(jax.value_and_grad(seq_loss))(one)
    g_devs = {d for leaf in jax.tree.leaves(grads) for d in leaf.devices()}
    loss_gap = abs(float(loss) - float(loss1))
    g_gap = max(_max_abs(a, b) for a, b in zip(jax.tree.leaves(grads),
                                               jax.tree.leaves(grads1)))
    g_scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads1))
    _require(hops > 0, "no collective-permute between stages")
    _require(len(g_devs) == num_stages, "gradients not spread over stages")
    # f32 at highest precision, same ops in the same order per stage
    _require(loss_gap <= 1e-6 * max(1.0, abs(float(loss1))), "loss mismatch")
    _require(g_gap <= 1e-5 * max(1.0, g_scale), "gradient mismatch")
    return {"stages": num_stages, "loss": float(loss), "seq_loss": float(loss1),
            "loss_gap": loss_gap, "grad_max_abs_gap": g_gap,
            "grad_scale": g_scale, "collective_permutes": hops,
            "grad_devices": len(g_devs)}


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-stage pipeline over four chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    import jax

    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    clock = _CompileClock()
    if args.four_chips:
        _run_phase("pipeline", phase_pipeline, clock, num_stages=4)
    else:
        _run_phase("scheduler", phase_scheduler, clock)
        _run_phase("ctr", phase_ctr, clock)
        _run_phase("kernels", phase_kernels, clock)
        _run_phase("serve", phase_serve, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
