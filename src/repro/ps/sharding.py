"""Sharded parameter-server table — HeterPS §3's CPU-PS tier, scaled out.

The paper keeps huge sparse embedding tables on CPU parameter servers and
shards them across hosts; workers pull only the touched rows and push
sparse row gradients back.  :class:`ShardedTable` vocab-partitions one
logical ``(V, D)`` table across ``N`` PS shards and **speaks the message
protocol** of :mod:`repro.ps.server` to them through a pluggable
:class:`~repro.ps.transport.Transport`:

* each shard is an endpoint owning one slab bucket — a
  :class:`~repro.ps.server.ShardServer` behind an in-process queue
  (default: deterministic, the tests/CI oracle path) or a real worker
  process (:class:`~repro.ps.transport.MultiprocTransport`);
* ``pull`` routes ids to their owners client-side, fans the per-shard
  requests out in one ``request_many`` round, and reassembles the rows
  in id order; ``push`` dedups duplicate ids via ``dedup_rows`` and
  pre-scales the update **client-side in jnp** (``-lr * summed_grads``),
  so the shard's f32 ``+=`` lands bit-identically to the single-table
  XLA scatter-add of the pre-refactor oracle (pinned in
  ``tests/test_ps.py`` / ``tests/test_ps_transport.py``);
* tier-aware placement stays **client-side**: a fixed-capacity
  **hot-row cache** (``hot_rows`` + an id→slot map) holds the rows the
  access monitor marked DEVICE-tier.  Pulls merge hot rows over the
  transport's cold rows; pushes write through to both, so the two stay
  bit-identical.  On TPU runtimes the cache lives in HBM
  (``memory_kind="device"``); shard slabs are the host/remote tier;
* every pull/push is metered per shard (bytes, rows, wall time) by an
  attached :class:`~repro.ps.telemetry.PSTelemetry` — with a real
  transport the timings now include the actual IPC hop; an optional
  simulated RPC latency still models a slower network on top.

The pre-refactor fused jnp kernels (:func:`sharded_pull`,
:func:`sharded_update` over one shard-major storage array) are kept
below as the reference implementation the message path is equivalence-
pinned against.  For elastic fleets (shards joining/leaving at runtime,
replicas, PS-hosted optimizers) see :mod:`repro.ps.elastic`.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.ps import dedup_rows
from repro.ps.transport import Transport, make_transport

#: tier codes stored in the per-shard placement arrays (int8); index-aligned
#: with ``repro.data.cache.Tier`` ordering DEVICE < HOST < DISK.
TIER_DEVICE, TIER_HOST, TIER_DISK = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class RoutingSpec:
    """Static routing metadata — hashable, so jit can close over it.

    ``partition="mod"`` (default) assigns row ``i`` to shard ``i % N`` —
    balanced under the zipf-skewed id streams of CTR logs.  ``"block"``
    assigns contiguous vocab ranges (shard ``s`` owns
    ``[s*block, (s+1)*block)``) — the layout a range-partitioned
    key-value PS would use.
    """

    vocab: int
    dim: int
    num_shards: int
    partition: str = "mod"

    def __post_init__(self):
        if self.partition not in ("mod", "block"):
            raise ValueError(f"unknown partition {self.partition!r}")
        if not 1 <= self.num_shards <= max(1, self.vocab):
            raise ValueError(
                f"num_shards={self.num_shards} outside [1, vocab={self.vocab}]")

    @property
    def block(self) -> int:
        return -(-self.vocab // self.num_shards)  # ceil

    @property
    def shard_rows(self) -> tuple[int, ...]:
        if self.partition == "mod":
            return tuple(
                (self.vocab - s + self.num_shards - 1) // self.num_shards
                for s in range(self.num_shards))
        return tuple(
            max(0, min(self.block, self.vocab - s * self.block))
            for s in range(self.num_shards))

    @property
    def offsets(self) -> tuple[int, ...]:
        """Slab start of each shard in the shard-major storage layout."""
        out, acc = [], 0
        for r in self.shard_rows:
            out.append(acc)
            acc += r
        return tuple(out)

    def route(self, ids):
        """ids → (owner shard, local row).  Works on jnp and np arrays."""
        if self.partition == "mod":
            return ids % self.num_shards, ids // self.num_shards
        block = self.block
        mod = jnp if isinstance(ids, jax.Array) else np
        return mod.clip(ids // block, 0, self.num_shards - 1), ids % block

    def flatten(self, ids):
        """ids → slot in the shard-major ``(V, D)`` storage array."""
        owner, local = self.route(ids)
        if isinstance(ids, jax.Array):
            return jnp.asarray(self.offsets, ids.dtype)[owner] + local
        return np.asarray(self.offsets, dtype=np.asarray(ids).dtype)[
            owner] + local

    def global_rows(self, shard: int) -> np.ndarray:
        """Global row ids owned by ``shard``, in local-row (slab) order."""
        if self.partition == "mod":
            return np.arange(shard, self.vocab, self.num_shards)
        lo = shard * self.block
        return np.arange(lo, lo + self.shard_rows[shard])


# --------------------------------------------------------------------------
# reference jnp kernels (pre-refactor single-array path — the oracle the
# message path is pinned against, and still the fused TPU formulation)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("spec",))
def sharded_pull(data, hot_rows, slot_of, ids, *, spec: RoutingSpec):
    """Routed pull over one shard-major ``(V, D)`` storage array: hot ids
    from the cache, cold ids from main storage.  Values are identical
    either way (write-through invariant), so the result is bit-identical
    to a single-table gather regardless of placement."""
    cold = data[spec.flatten(ids)]
    if hot_rows is None or hot_rows.shape[0] == 0:
        return cold
    slot = slot_of[ids]
    hot = hot_rows[jnp.clip(slot, 0)]
    return jnp.where((slot >= 0)[..., None], hot, cold)


@functools.partial(jax.jit, static_argnames=("spec", "dedup"))
def sharded_update(data, ids, row_grads, lr, *, spec: RoutingSpec,
                   dedup: bool = True):
    """Routed push into shard-major storage: one COO scatter-add of
    ``-lr * row_grads`` at the ids' storage slots.

    With ``dedup`` the (ids, grads) stream is first reduced to one summed
    row per distinct id (``dedup_rows``); padding slots carry the id
    ``spec.vocab`` and are mapped past the end of storage, so the scatter
    drops them — no masked zero-adds, hence per-row accumulation order
    (and bits) matches the single-table scatter exactly.  Returns
    ``(new_data, pushed_ids, summed_updates)``.
    """
    ids = ids.reshape(-1)
    g = row_grads.reshape(-1, spec.dim)
    if dedup:
        ids, g = dedup_rows(ids, g, fill_id=spec.vocab)
    u = (-lr * g).astype(data.dtype)
    tgt = jnp.where(ids < spec.vocab, spec.flatten(ids), data.shape[0])
    return data.at[tgt].add(u, mode="drop"), ids, u


@functools.partial(jax.jit, static_argnames=("dedup", "vocab", "dim"))
def _client_update(ids, row_grads, lr, *, vocab: int, dim: int,
                   dedup: bool = True):
    """Client half of a push: dedup + pre-scale in jnp, exactly as
    :func:`sharded_update` would — the shard's ``+=`` of the result is
    then the same IEEE add as the oracle's scatter.  Returns
    ``(pushed_ids, updates)`` (padding ids carry ``vocab``)."""
    ids = ids.reshape(-1)
    g = row_grads.reshape(-1, dim)
    if dedup:
        ids, g = dedup_rows(ids, g, fill_id=vocab)
    return ids, (-lr * g).astype(jnp.float32)


@jax.jit
def _hot_apply(hot_rows, slot_of, ids, updates):
    """Write-through: apply the already-summed push updates to the cached
    copies of hot rows (cold / padding ids drop)."""
    slot = slot_of[ids]
    tgt = jnp.where(slot >= 0, slot, hot_rows.shape[0])
    return hot_rows.at[tgt].add(updates, mode="drop")


@jax.jit
def _merge_hot(cold, hot_rows, slot_of, ids):
    """Overlay hot-cache rows onto transport-pulled cold rows (selection
    only — bit-neutral under the write-through invariant)."""
    slot = slot_of[ids]
    hot = hot_rows[jnp.clip(slot, 0)]
    return jnp.where((slot >= 0)[..., None], hot, cold)


class ShardedTable:
    """One logical embedding table, vocab-partitioned across N PS shards
    behind a :class:`~repro.ps.transport.Transport`.

    Parameters:
      transport: ``None`` (→ in-process queue backend), ``"inproc"`` /
        ``"multiproc"``, or a :class:`Transport` instance.  Shard ``s``
        becomes endpoint ``s`` owning bucket ``s`` (its slab).
      monitor: optional :class:`repro.data.cache.AccessMonitor` — every
        pull records row-access counts (the data-management module's
        input signal).
      telemetry: optional :class:`repro.ps.telemetry.PSTelemetry` —
        per-shard pull/push bytes + wall-time accounting.
      hot_capacity: row capacity of the hot cache (0 disables it until a
        :class:`~repro.ps.placement.TierPlacer` is attached anyway —
        the cache only fills on re-pin).
      rpc_latency_s: extra simulated per-op worker↔PS latency on top of
        the transport's real cost (the overlap benchmark calibrates it
        to model the paper's cross-host network on a single box).

    Thread-safety: pulls snapshot the (hot cache, slot map) pair under
    ``_mu``; pushes and hot-cache re-pins serialize on ``_update_mu`` so
    a re-pin landing mid-push can neither lose nor double-apply a
    write-through (pulls stay wait-free — they may observe a push's
    shard-side effect before its hot write-through, the same bounded
    staleness the async client already trades on).
    """

    def __init__(self, vocab: int, dim: int, num_shards: int, key=None, *,
                 partition: str = "mod", dtype=jnp.float32, monitor=None,
                 telemetry=None, hot_capacity: int = 4096,
                 rpc_latency_s: float = 0.0, init_scale: float | None = None,
                 transport: str | Transport | None = None):
        self.spec = RoutingSpec(vocab, dim, num_shards, partition)
        self.monitor = monitor
        self.telemetry = telemetry
        self.hot_capacity = int(hot_capacity)
        self.rpc_latency_s = float(rpc_latency_s)
        self.dtype = dtype
        self._mu = threading.Lock()
        self._update_mu = threading.RLock()
        self.transport = make_transport(transport)
        for s in range(num_shards):
            self.transport.add_shard(s, dim=dim, optimizer="none")
        if key is not None:
            scale = dim**-0.5 if init_scale is None else init_scale
            dense = jax.random.normal(key, (vocab, dim), dtype) * scale
            self._load_dense(dense)
        else:
            self._load_dense(jnp.zeros((vocab, dim), dtype))
        # hot-row cache: empty until the first re-pin
        self.hot_rows = jnp.zeros((0, dim), dtype)
        self.slot_of = jnp.full((vocab + 1,), -1, jnp.int32)
        # simulated storage-tier placement (row granularity, per shard);
        # everything starts cold, matching a freshly loaded table
        self.tiers = [np.full((r,), TIER_DISK, np.int8)
                      for r in self.spec.shard_rows]
        # host copy of the slot map for O(ids) hot-hit accounting — counts
        # rows actually served from the cache, not merely DEVICE-coded
        self._slot_np = np.full((vocab + 1,), -1, np.int32)
        self._cache_active = False

    # --- construction / inspection ------------------------------------
    def _load_dense(self, dense) -> None:
        """Ship a vocab-order ``(V, D)`` table to the shards as slabs."""
        dense_np = np.asarray(dense, np.float32)
        for s in range(self.spec.num_shards):
            self.transport.request(s, {
                "op": "create", "bucket": s,
                "rows": dense_np[self.spec.global_rows(s)]})

    @classmethod
    def from_dense(cls, table, num_shards: int, *, partition: str = "mod",
                   **kw) -> "ShardedTable":
        t = cls(table.shape[0], table.shape[1], num_shards,
                partition=partition, dtype=table.dtype, **kw)
        t._load_dense(table)
        return t

    def to_dense(self):
        """Reassemble the logical ``(V, D)`` table (tests/checkpointing)."""
        dense = np.empty((self.vocab, self.dim), np.float32)
        replies = self.transport.request_many(
            [(s, {"op": "snapshot", "bucket": s})
             for s in range(self.num_shards)])
        for s, rep in enumerate(replies):
            dense[self.spec.global_rows(s)] = rep["rows"]
        return jnp.asarray(dense, self.dtype)

    @property
    def shards(self) -> list:
        """Per-shard slab snapshots (local-row order)."""
        return [jnp.asarray(rep["rows"], self.dtype)
                for rep in self.transport.request_many(
                    [(s, {"op": "snapshot", "bucket": s})
                     for s in range(self.num_shards)])]

    @property
    def vocab(self) -> int:
        return self.spec.vocab

    @property
    def dim(self) -> int:
        return self.spec.dim

    @property
    def num_shards(self) -> int:
        return self.spec.num_shards

    # --- transport routing ----------------------------------------------
    def _shard_messages(self, op: str, ids_flat: np.ndarray,
                        payload: np.ndarray | None = None, **extra):
        """Group a flat id stream by owner shard into per-shard messages.
        Returns ``(messages, segments)`` where ``segments[i]`` are the
        positions in ``ids_flat`` message ``i`` covers."""
        owner, local = self.spec.route(ids_flat)
        order = np.argsort(owner, kind="stable")
        counts = np.bincount(owner, minlength=self.spec.num_shards)
        msgs, segs, start = [], [], 0
        for s in range(self.spec.num_shards):
            n = int(counts[s])
            if n == 0:
                continue
            seg = order[start:start + n]
            start += n
            msg = {"op": op, "buckets": np.full((n,), s, np.int64),
                   "ids": local[seg], **extra}
            if payload is not None:
                msg["updates" if op == "add" else "grads"] = payload[seg]
            msgs.append((s, msg))
            segs.append(seg)
        return msgs, segs

    def _fetch(self, ids_flat: np.ndarray) -> np.ndarray:
        """Raw routed pull over the transport (no metering, no cache) —
        rows in ``ids_flat`` order."""
        msgs, segs = self._shard_messages("pull", ids_flat)
        out = np.empty((ids_flat.size, self.dim), np.float32)
        for seg, rep in zip(segs, self.transport.request_many(msgs)):
            out[seg] = rep["rows"]
        return out

    # --- PS operations -------------------------------------------------
    def _account(self, op: str, ids_np: np.ndarray, seconds: float,
                 bytes_per_row: int) -> None:
        if self.telemetry is None:
            return
        owner, local = self.spec.route(ids_np)
        owner, local = owner.ravel(), local.ravel()
        S = self.spec.num_shards
        per_shard = np.bincount(owner, minlength=S)
        hot = None
        if self._cache_active:
            hot = np.bincount(
                owner[self._slot_np[ids_np.ravel()] >= 0], minlength=S)
        self.telemetry.record(op, rows=per_shard,
                              bytes_=per_shard * bytes_per_row,
                              seconds=seconds, hot_rows=hot)

    def _check_ids(self, ids_np: np.ndarray) -> None:
        if ids_np.size and (ids_np.min() < 0 or ids_np.max() >= self.vocab):
            raise ValueError(
                f"ids out of range for vocab={self.vocab}: "
                f"[{ids_np.min()}, {ids_np.max()}]")

    def pull(self, ids):
        """PS pull: fetch the touched rows.  ``ids (...,)`` → ``(..., D)``."""
        t0 = time.perf_counter()
        ids = jnp.asarray(ids)
        ids_np = np.asarray(ids)
        self._check_ids(ids_np)
        if self.monitor is not None:
            self.monitor.record(ids_np)
        cold = self._fetch(ids_np.ravel().astype(np.int64))
        out = jnp.asarray(cold.reshape(ids_np.shape + (self.dim,)),
                          self.dtype)
        with self._mu:   # coherent (cache, slot-map) snapshot
            hot, slot = self.hot_rows, self.slot_of
        if hot.shape[0]:
            out = _merge_hot(out, hot, slot, ids)
        jax.block_until_ready(out)
        if self.rpc_latency_s:
            time.sleep(self.rpc_latency_s)
        self._account("pull", ids_np, time.perf_counter() - t0,
                      self.spec.dim * out.dtype.itemsize)
        return out

    def push(self, ids, row_grads, *, lr: float, dedup: bool = True):
        """PS push: apply ``-lr * row_grads`` at the owning shards (and
        write through to the hot cache, keeping the two bit-identical).

        The dedup + ``-lr`` pre-scale runs client-side in jnp (identical
        to the oracle's :func:`sharded_update` prologue); shards apply
        the summed per-row updates with a plain f32 add."""
        t0 = time.perf_counter()
        ids = jnp.asarray(ids)
        ids_np = np.asarray(ids)
        self._check_ids(ids_np)
        grads = jnp.asarray(row_grads)
        pushed_ids, updates = _client_update(
            ids, grads, lr, vocab=self.vocab, dim=self.dim, dedup=dedup)
        jax.block_until_ready(updates)
        pushed_np = np.asarray(pushed_ids)
        u_np = np.asarray(updates)
        live = pushed_np < self.vocab        # drop dedup padding slots
        wire_ids = pushed_np[live].astype(np.int64)
        with self._update_mu:
            msgs, _ = self._shard_messages("add", wire_ids,
                                           payload=u_np[live])
            self.transport.request_many(msgs)
            # write-through must see the *current* cache/slot-map (a
            # re-pin serializes on _update_mu, so it can't land between
            # the shard apply and this update)
            with self._mu:
                if self.hot_rows.shape[0]:
                    self.hot_rows = jax.block_until_ready(_hot_apply(
                        self.hot_rows, self.slot_of, pushed_ids, updates))
        if self.rpc_latency_s:
            time.sleep(self.rpc_latency_s)
        if self.telemetry is not None:
            itemsize = np.dtype(np.float32).itemsize
            # the wire carries one summed row per distinct id when
            # deduping; raw duplicates otherwise
            acct_ids = wire_ids if dedup else ids_np
            self._account("push", acct_ids, time.perf_counter() - t0,
                          self.spec.dim * itemsize + ids_np.itemsize)
        return self

    # --- tier placement (written by TierPlacer) -------------------------
    def set_tiers(self, global_tiers: np.ndarray) -> dict:
        """Install a per-row tier assignment (array of
        ``repro.data.cache.Tier`` over the *global* vocab) into the
        per-shard tier arrays; returns per-tier row counts."""
        from repro.data.cache import Tier

        codes = np.full((self.vocab,), TIER_DISK, np.int8)
        codes[global_tiers == Tier.DEVICE] = TIER_DEVICE
        codes[global_tiers == Tier.HOST] = TIER_HOST
        for s in range(self.num_shards):
            self.tiers[s] = codes[self.spec.global_rows(s)]
        return {
            "device_rows": int((codes == TIER_DEVICE).sum()),
            "host_rows": int((codes == TIER_HOST).sum()),
            "disk_rows": int((codes == TIER_DISK).sum()),
        }

    def install_hot_rows(self, hot_ids: np.ndarray) -> int:
        """Re-pin: load ``hot_ids`` (truncated to capacity) into the hot
        cache and rebuild the slot map.  Returns the cached row count."""
        hot_ids = np.asarray(hot_ids, np.int64).ravel()[:self.hot_capacity]
        if hot_ids.size == 0:
            return 0
        slot = np.full((self.vocab + 1,), -1, np.int32)
        slot[hot_ids] = np.arange(hot_ids.size, dtype=np.int32)
        slot_j = jnp.asarray(slot)
        # pad the cache to its fixed capacity so repins with different hot
        # set sizes don't retrigger jit traces of the pull/push paths
        pad = np.zeros((self.hot_capacity,), np.int64)
        pad[:hot_ids.size] = hot_ids
        with self._update_mu:    # no push between fetch and install
            rows = jnp.asarray(self._fetch(pad), self.dtype)
            with self._mu:
                self.hot_rows = _to_memory_kind(rows, "device")
                self.slot_of = slot_j
                self._slot_np = slot
                self._cache_active = True
        return int(hot_ids.size)

    def demote_storage(self) -> None:
        """Tiering hint: shard slabs are the cold tier once the hot cache
        covers the head of the distribution.  Client-side this is a
        broadcast notification — on CPU shard servers it is a no-op; a
        TPU/accelerator shard would move its slab off-device."""
        self.transport.request_many(
            [(s, {"op": "demote"}) for s in sorted(
                self.transport.live_shards)])

    def tier_counts(self) -> np.ndarray:
        """(num_shards, 3) rows per (DEVICE, HOST, DISK) tier per shard."""
        return np.stack([np.bincount(t, minlength=3) for t in self.tiers])

    def close(self) -> None:
        """Shut the shard endpoints down (idempotent)."""
        self.transport.close()
        if self.telemetry is not None:
            self.telemetry.close()


def _to_memory_kind(arr, kind: str):
    """device_put with a memory kind on a TPU; identity elsewhere — the
    CPU simulates tiers in software.  On a TPU a placement the runtime
    refuses raises: the hot cache must really land in HBM."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return arr
    sharding = jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)
    return jax.device_put(arr, sharding)
