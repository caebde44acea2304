"""Reduced CTR-over-PS workload (HeterPS §6's sparse workload, scaled to
the CPU container) — shared by ``launch/train.py --sparse-ps``,
``benchmarks/bench_ps.py`` and the PS tests.

One step: pull the batch's embedding rows from the sharded PS, run a
dense tower on the concatenated slot embeddings, push the row gradients
back.  :func:`train_ctr_ps` drives it either *synchronously*
(pull → compute → push, the baseline) or *asynchronously* through
:class:`~repro.ps.client.PSClient` (double-buffered overlap), with the
tier placer re-pinning hot rows on a fixed cadence.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import AccessMonitor, PrefetchLoader
from repro.ps.client import PSClient
from repro.ps.elastic import ElasticPSFleet, PSUnrecoverable
from repro.ps.faults import FaultInjector
from repro.ps.placement import TierPlacer
from repro.ps.sharding import ShardedTable
from repro.ps.snapshot import FleetCheckpointer, load_fleet_checkpoint
from repro.ps.telemetry import PSTelemetry
from repro.ps.transport import make_transport


@dataclasses.dataclass(frozen=True)
class CTRConfig:
    """Criteo-style reduced CTR model: 26 sparse slots → dense tower."""

    vocab: int = 200_000
    emb_dim: int = 16
    slots: int = 26
    tower: tuple[int, ...] = (512, 512, 256)
    batch: int = 256
    seed: int = 0
    lr: float = 0.05
    emb_lr_scale: float = 10.0   # sparse rows see few updates each → hotter lr


def click_stream(cfg: CTRConfig) -> Iterator[dict]:
    """Synthetic click log: zipf-ish sparse ids (hot head, long tail —
    drives the tier monitor) with a planted logistic structure so the
    logloss actually decreases."""
    rng = np.random.default_rng(cfg.seed)
    w_true = rng.standard_normal(cfg.slots) * 0.7
    while True:
        ids = (rng.pareto(1.2, (cfg.batch, cfg.slots)) * 1000).astype(
            np.int64) % cfg.vocab
        sig = (np.sin(ids % 97) * w_true).sum(-1)
        y = (sig + rng.standard_normal(cfg.batch) * 0.5 > 0)
        yield {"ids": ids.astype(np.int32),
               "label": y.astype(np.float32)}


def init_tower(cfg: CTRConfig, key) -> dict:
    dims = (cfg.slots * cfg.emb_dim,) + tuple(cfg.tower) + (1,)
    keys = jax.random.split(key, len(dims) - 1)
    return {
        "w": [jax.random.normal(k, (a, b)) * (a**-0.5)
              for k, (a, b) in zip(keys, itertools.pairwise(dims))],
        "b": [jnp.zeros((b,)) for b in dims[1:]],
    }


def make_step_fn(cfg: CTRConfig):
    """jitted ``(tower, emb_rows, labels) → (tower', emb_row_grads, loss)``.

    The embedding rows enter as a *pulled* activation ``(B, slots, D)``;
    differentiating w.r.t. them yields exactly the per-row gradients the
    PS push wants — the table itself never crosses the jit boundary.
    """

    def bce(logit, y):
        return jnp.mean(jnp.maximum(logit, 0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    def loss_fn(tower, emb, labels):
        h = emb.reshape(emb.shape[0], cfg.slots * cfg.emb_dim)
        for i, (w, b) in enumerate(zip(tower["w"], tower["b"])):
            h = h @ w + b
            if i < len(tower["w"]) - 1:
                h = jnp.tanh(h)
        return bce(h[:, 0], labels)

    def step(tower, emb, labels):
        loss, (g_tower, g_emb) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(tower, emb, labels)
        tower = jax.tree.map(lambda p, g: p - cfg.lr * g, tower, g_tower)
        return tower, g_emb, loss

    return jax.jit(step)


def make_table(cfg: CTRConfig, num_shards: int, *,
               partition: str = "mod", rpc_latency_s: float = 0.0,
               with_monitor: bool = True, transport=None) -> ShardedTable:
    return ShardedTable(
        cfg.vocab, cfg.emb_dim, num_shards,
        jax.random.PRNGKey(cfg.seed), init_scale=0.05, partition=partition,
        monitor=AccessMonitor(cfg.vocab) if with_monitor else None,
        telemetry=PSTelemetry(num_shards), rpc_latency_s=rpc_latency_s,
        transport=transport)


def make_fleet(cfg: CTRConfig, num_shards: int, *,
               optimizer: str = "sgd", transport=None,
               staleness_bound: int = 8,
               rpc_latency_s: float = 0.0) -> ElasticPSFleet:
    return ElasticPSFleet(
        cfg.vocab, cfg.emb_dim, num_shards=num_shards, optimizer=optimizer,
        transport=transport, telemetry=PSTelemetry(num_shards),
        key=jax.random.PRNGKey(cfg.seed), init_scale=0.05,
        staleness_bound=staleness_bound, rpc_latency_s=rpc_latency_s)


def train_ctr_ps(cfg: CTRConfig | None = None, *, steps: int = 200,
                 num_shards: int = 4, mode: str = "async",
                 partition: str = "mod", rpc_latency_s: float = 0.0,
                 repin_interval: int = 50, depth: int = 2,
                 log_every: int = 0, transport=None) -> dict:
    """Train the reduced CTR model over the sharded PS.

    ``mode="sync"``: pull → compute → push each step (the baseline the
    overlap benchmark compares against).  ``mode="async"``: the
    :class:`PSClient` double-buffers pulls and pushes around the compute.
    Returns a summary with per-step wall times, losses, tier stats and
    the telemetry report.
    """
    if mode not in ("sync", "async"):
        raise ValueError(f"mode must be sync|async, got {mode!r}")
    cfg = cfg or CTRConfig()
    table = make_table(cfg, num_shards, partition=partition,
                       rpc_latency_s=rpc_latency_s, transport=transport)
    placer = TierPlacer(table, table.monitor, interval=repin_interval)
    step_fn = make_step_fn(cfg)
    tower = init_tower(cfg, jax.random.PRNGKey(cfg.seed + 1))
    emb_lr = cfg.lr * cfg.emb_lr_scale

    losses: list[float] = []
    times: list[float] = []
    ts: list[float] = []        # absolute per-step finish times (for
    t_start = time.perf_counter()  # steady-state rate measurement)

    if mode == "sync":
        stream = click_stream(cfg)
        for i in range(steps):
            t0 = time.perf_counter()
            b = next(stream)
            rows = table.pull(b["ids"])
            tower, g_emb, loss = step_fn(tower, rows,
                                         jnp.asarray(b["label"]))
            table.push(b["ids"], jax.block_until_ready(g_emb), lr=emb_lr)
            placer.step(i)
            losses.append(float(loss))
            times.append(time.perf_counter() - t0)
            ts.append(time.perf_counter() - t_start)
            if log_every and i % log_every == 0:
                print(f"step {i:4d} logloss {losses[-1]:.4f} "
                      f"({times[-1] * 1e3:.1f} ms)", flush=True)
    else:
        loader = PrefetchLoader(
            itertools.islice(click_stream(cfg), steps), depth=depth)
        client = PSClient(table, loader, ids_key="ids", depth=depth)
        try:
            for i, (b, rows) in enumerate(client):
                t0 = time.perf_counter()
                tower, g_emb, loss = step_fn(tower, rows,
                                             jnp.asarray(b["label"]))
                client.push(b["ids"], jax.block_until_ready(g_emb),
                            lr=emb_lr)
                placer.step(i)
                losses.append(float(loss))
                times.append(time.perf_counter() - t0)
                ts.append(time.perf_counter() - t_start)
                if log_every and i % log_every == 0:
                    print(f"step {i:4d} logloss {losses[-1]:.4f} "
                          f"({times[-1] * 1e3:.1f} ms)", flush=True)
        finally:
            client.close()
            loader.close()

    wall = time.perf_counter() - t_start
    tel = table.telemetry.totals()
    # cost-model bridge: the measured PS traffic re-anchors the CPU
    # resource type's bandwidth terms and yields a measured embedding-layer
    # ODT (the LayerProfile shape the scheduler's cost model consumes)
    from repro.core.resources import CPU_CORE

    measured_res = table.telemetry.to_resource(CPU_CORE)
    odt_sync, odt_act = table.telemetry.embedding_odt(len(losses) * cfg.batch)
    table.close()
    return {
        "mode": mode, "steps": len(losses), "num_shards": num_shards,
        "first_loss": losses[0], "last_loss": losses[-1],
        "loss_decreased": losses[-1] < losses[0],
        "seconds": wall,
        "step_times": times,
        "step_ts": ts,
        "steps_per_sec": len(losses) / wall if wall > 0 else 0.0,
        "repins": placer.repins,
        "tier_stats": placer.last_stats,
        "pull_gb": tel["pull"]["bytes"] / 1e9,
        "push_gb": tel["push"]["bytes"] / 1e9,
        "pull_bw_gbs": tel["pull"]["bandwidth"] / 1e9,
        "push_bw_gbs": tel["push"]["bandwidth"] / 1e9,
        "hot_pull_fraction": tel["pull"]["hot_fraction"],
        "measured_ingest_bw": measured_res.ingest_bw,
        "measured_net_bw": measured_res.net_bw,
        "embedding_odt_sync": odt_sync,
        "embedding_odt_act": odt_act,
    }


def train_ctr_elastic(cfg: CTRConfig | None = None, *, steps: int = 200,
                      num_shards: int = 3, optimizer: str = "sgd",
                      transport=None, mode: str = "sync",
                      events: list[tuple[int, str, int | None]] | None = None,
                      staleness_bound: int = 8, depth: int = 2,
                      rpc_latency_s: float = 0.0,
                      fault_schedule=None, fault_seed: int = 0,
                      ckpt_dir: str | None = None, ckpt_every: int = 0,
                      ckpt_keep: int = 2, max_restores: int = 4,
                      replan=None,
                      log_every: int = 0) -> dict:
    """Train the reduced CTR model over an **elastic** PS fleet, with
    scripted fleet events injected mid-training.

    ``events`` is a list of ``(step, action, shard)`` where ``action`` is
    ``"join"`` (shard ignored), ``"kill"`` or ``"leave"`` — e.g.
    ``[(40, "join", None), (80, "kill", 0)]`` grows the fleet at step 40
    and hard-kills shard 0 at step 80 (replica recovery kicks in on the
    next touch).  Training never pauses: the loop keeps issuing
    pull/push through every event.

    The sync replication + deterministic PS-hosted optimizer make the
    run's loss trajectory **bit-equal** (``mode="sync"``) to the same run
    without any events — the acceptance pin for lossless recovery.
    Returns the per-step ``losses`` so callers can compare trajectories.

    Chaos knobs: ``fault_schedule`` (anything
    :func:`repro.ps.faults.parse_schedule` accepts) wraps the transport
    in a seeded :class:`~repro.ps.faults.FaultInjector`.  ``ckpt_dir`` +
    ``ckpt_every`` arm periodic unified checkpoints
    (:class:`~repro.ps.snapshot.FleetCheckpointer`); on a correlated
    primary+backup loss (:class:`PSUnrecoverable`) the loop restores the
    newest checkpoint, rewinds the (deterministic) batch stream to its
    cursor and **replays** — the loss trajectory from the restore step
    is bit-equal to a fault-free run (sync mode; pinned in
    tests/test_chaos.py).

    ``replan`` is a factory ``fleet -> ReplanController`` (see
    ``core/replan.py``): the controller is built once the fleet exists,
    ``observe()``-d after every step (step-driven windows — the training
    loop stays single-threaded), and its :meth:`report` lands in the
    result under ``"replan"``.  A factory rather than a controller keeps
    this module free of scheduler imports.
    """
    if mode not in ("sync", "async"):
        raise ValueError(f"mode must be sync|async, got {mode!r}")
    if ckpt_dir and ckpt_every and mode != "sync":
        raise ValueError("checkpoint/restore replay requires mode='sync' "
                         "(async pipelines have no exact cursor)")
    cfg = cfg or CTRConfig()
    if fault_schedule is not None:
        transport = FaultInjector(make_transport(transport), fault_schedule,
                                  seed=fault_seed)
    fleet = make_fleet(cfg, num_shards, optimizer=optimizer,
                       transport=transport, staleness_bound=staleness_bound,
                       rpc_latency_s=rpc_latency_s)
    by_step: dict[int, list[tuple[str, int | None]]] = {}
    for step, action, shard in (events or []):
        by_step.setdefault(int(step), []).append((action, shard))

    def fire(i: int) -> None:
        for action, shard in by_step.get(i, []):
            if action == "join":
                fleet.join()
            elif action == "kill":
                if shard in fleet.transport.live_shards:
                    fleet.kill(shard)
            elif action == "leave":
                if shard in fleet.transport.live_shards:
                    fleet.leave(shard)
            else:
                raise ValueError(f"unknown fleet event {action!r}")

    controller = replan(fleet) if replan is not None else None
    step_fn = make_step_fn(cfg)
    tower = init_tower(cfg, jax.random.PRNGKey(cfg.seed + 1))
    # the fleet's PS-hosted optimizer applies the lr server-side, so the
    # pushed payload is the raw (deduped, summed) gradient
    emb_lr = cfg.lr * cfg.emb_lr_scale
    losses: list[float] = []
    ts: list[float] = []
    t_start = time.perf_counter()

    restores = 0
    ckpt: FleetCheckpointer | None = None
    if mode == "sync":
        if ckpt_dir and ckpt_every:
            ckpt = FleetCheckpointer(fleet, ckpt_dir, every=ckpt_every,
                                     keep=ckpt_keep)
        stream = click_stream(cfg)
        i = 0
        while i < steps:
            try:
                b = next(stream)
                rows = fleet.pull(b["ids"])
                tower, g_emb, loss = step_fn(tower, rows,
                                             jnp.asarray(b["label"]))
                fleet.push(b["ids"], jax.block_until_ready(g_emb),
                           lr=emb_lr)
                fire(i)
                losses.append(float(loss))
                ts.append(time.perf_counter() - t_start)
                if controller is not None:
                    controller.observe(num_examples=cfg.batch)
                if ckpt is not None:
                    # post-step state: fleet slabs + tower + cursor i+1
                    ckpt.maybe_save(i, tower, metadata={"cursor": i + 1,
                                                        "seed": cfg.seed})
                if log_every and i % log_every == 0:
                    print(f"step {i:4d} logloss {losses[-1]:.4f}",
                          flush=True)
                i += 1
            except PSUnrecoverable:
                # correlated primary+backup loss — replica promotion is
                # out of moves; restore the newest unified checkpoint
                # and replay the deterministic stream from its cursor
                if ckpt is None or restores >= max_restores:
                    raise
                restores += 1
                ckpt.wait()
                try:
                    tower, snap, step0, _ = load_fleet_checkpoint(
                        ckpt_dir, params_template=tower)
                except FileNotFoundError:
                    raise  # nothing durable yet — genuinely lost
                fleet.restore_snapshot(snap)
                del losses[step0 + 1:]
                del ts[step0 + 1:]
                stream = click_stream(cfg)
                for _ in range(step0 + 1):   # skip replayed batches
                    next(stream)
                i = step0 + 1
                if log_every:
                    print(f"restored checkpoint step {step0}, replaying "
                          f"from step {i}", flush=True)
        if ckpt is not None:
            ckpt.wait()
    else:
        loader = PrefetchLoader(
            itertools.islice(click_stream(cfg), steps), depth=depth)
        client = PSClient(fleet, loader, ids_key="ids", depth=depth)
        try:
            for i, (b, rows) in enumerate(client):
                tower, g_emb, loss = step_fn(tower, rows,
                                             jnp.asarray(b["label"]))
                client.push(b["ids"], jax.block_until_ready(g_emb),
                            lr=emb_lr)
                fire(i)
                losses.append(float(loss))
                ts.append(time.perf_counter() - t_start)
                if controller is not None:
                    controller.observe(num_examples=cfg.batch)
        finally:
            client.close()
            loader.close()

    wall = time.perf_counter() - t_start
    tel = fleet.telemetry.totals()
    fleet_events = list(fleet.events)
    stats = fleet.stats()
    tr = fleet.transport
    transport_counters = dict(tr.counters)
    injections: list[dict] = []
    if isinstance(tr, FaultInjector):
        injections = list(tr.injections)
        for k, v in tr.inner.counters.items():
            transport_counters[k] = transport_counters.get(k, 0) + v
    fleet.close()
    recoveries = [e for e in fleet_events if e["kind"] == "recover"]
    joins = [e for e in fleet_events if e["kind"] == "join"]
    replan_report = controller.report() if controller is not None else None
    return {
        "replan": replan_report,
        "mode": mode, "steps": len(losses), "optimizer": optimizer,
        "first_loss": losses[0], "last_loss": losses[-1],
        "loss_decreased": losses[-1] < losses[0],
        "losses": losses,
        "seconds": wall,
        "step_ts": ts,
        "steps_per_sec": len(losses) / wall if wall > 0 else 0.0,
        "live_shards": stats["live_shards"],
        "events": fleet_events,
        "recovery_seconds": sum(e["seconds"] for e in recoveries),
        "join_seconds": sum(e["seconds"] for e in joins),
        "restores": restores,
        "checkpoints": list(ckpt.saved) if ckpt is not None else [],
        "injections": injections,
        "transport_counters": transport_counters,
        "pull_gb": tel["pull"]["bytes"] / 1e9,
        "push_gb": tel["push"]["bytes"] / 1e9,
        # where the dense tower trained: ["tpu"] on the chip
        "tower_platforms": sorted({d.platform for leaf in jax.tree.leaves(tower)
                                   for d in leaf.devices()}),
    }
