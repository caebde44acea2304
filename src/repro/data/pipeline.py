"""Data pipeline — HeterPS data-management module (§3), training-data side.

The paper stores training data in an HDFS cluster, prefetches batches
into CPU-worker memory, and spills to SSD when RAM is tight.  Here:

* :class:`SyntheticTokenDataset` — deterministic synthetic LM batches
  (seeded per-step PRNG; reproducible across restarts and host counts);
* :class:`PrefetchLoader` — background-thread prefetch with a bounded
  queue (the paper's prefetch-and-cache behaviour); its waits and the
  worker's batch making are ``data.*`` spans (:mod:`repro.obs.scopes`);
* :func:`shard_batch` — places a host batch onto the mesh with the batch
  axis sharded over ``("pod", "data")``.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import scopes
from repro.obs.trace import span


class SyntheticTokenDataset:
    """Deterministic synthetic next-token-prediction batches.

    Step ``i`` is a pure function of (seed, i) — restart-safe, and every
    host can generate its own shard without coordination.

    Tokens are drawn from a Zipfian unigram distribution
    (``p(t) ∝ 1/(t+1)^a``, the natural-language shape), not uniform:
    with i.i.d. *uniform* tokens the cross-entropy floor is ``log V`` and
    the only achievable descent is flattening the initial logit variance
    — a signal small enough that batch noise buries it for some archs
    (the OLMoE plateau, see DESIGN.md §MoE kernels).  A skewed marginal
    gives training real, quickly-learnable headroom
    (``H(zipf) ≪ log V``) so "loss decreases" measures optimization, not
    luck.  ``zipf_a=0`` restores uniform sampling.
    """

    def __init__(self, vocab: int, batch_size: int, seq_len: int, *,
                 seed: int = 0, context_len: int = 0, d_model: int = 0,
                 zipf_a: float = 1.2):
        self.vocab = vocab
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.seed = seed
        self.context_len = context_len
        self.d_model = d_model
        if zipf_a:
            w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_a
            self._probs = w / w.sum()
        else:
            self._probs = None

    def batch(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        shape = (self.batch_size, self.seq_len + 1)
        if self._probs is None:
            toks = rng.integers(0, self.vocab, shape, dtype=np.int32)
        else:
            toks = rng.choice(self.vocab, size=shape,
                              p=self._probs).astype(np.int32)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.context_len:
            out["context"] = rng.standard_normal(
                (self.batch_size, self.context_len, self.d_model)
            ).astype(np.float32)
        return out

    def __iter__(self) -> Iterator[dict]:
        i = 0
        while True:
            yield self.batch(i)
            i += 1


#: queue marker ending the stream — lets a consumer blocked in ``get()``
#: observe producer shutdown instead of hanging forever
_SENTINEL = object()


class PrefetchLoader:
    """Background prefetch with a bounded queue (HeterPS prefetches input
    data into worker memory ahead of the consuming stage).

    Shutdown contract: the worker only ever blocks in *timed* puts, so it
    observes ``close()`` promptly even when the queue is full; on exit
    (dataset exhausted or ``close()``) it always enqueues a sentinel, so a
    consumer blocked in ``__next__`` wakes up and gets ``StopIteration``
    rather than hanging on an empty queue.
    """

    def __init__(self, dataset, depth: int = 2, put_timeout: float = 0.05):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._put_timeout = put_timeout

        def worker():
            try:
                batches = iter(dataset)
                while True:
                    with span(scopes.DATA_PRODUCE):
                        b = next(batches, _SENTINEL)
                    if b is _SENTINEL:
                        return
                    placed = False
                    with span(scopes.DATA_PUT_WAIT):
                        while not self._stop.is_set():
                            try:
                                self._q.put(b, timeout=self._put_timeout)
                                placed = True
                                break
                            except queue.Full:
                                continue
                    if not placed:
                        return  # close() requested while queue stayed full
            finally:
                # Always terminate the stream.  If close() was requested and
                # the queue is full, make room by dropping buffered batches
                # (the consumer is gone).  Without close() we must not drop
                # data — a slow consumer may still drain — so back off
                # exponentially instead of spinning while we wait for room.
                wait = self._put_timeout
                while True:
                    try:
                        self._q.put(_SENTINEL, timeout=wait)
                        return
                    except queue.Full:
                        if self._stop.is_set():
                            try:
                                self._q.get_nowait()
                            except queue.Empty:
                                pass
                        else:
                            wait = min(wait * 2, 1.0)

        self._t = threading.Thread(target=worker, daemon=True)
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        with span(scopes.DATA_WAIT):
            item = self._q.get()
        if item is _SENTINEL:
            self._done = True
            raise StopIteration
        return item

    def close(self, timeout: float = 5.0):
        """Stop the worker; safe to call repeatedly / with a blocked consumer."""
        self._stop.set()
        self._t.join(timeout)


def shard_batch(batch: dict, mesh, batch_axes=("pod", "data")) -> dict:
    """Device-put a host batch with the batch dim sharded over the data
    axes of the mesh (replicated on the model axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def put(x):
        spec = P(axes, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return {k: put(jnp.asarray(v)) for k, v in batch.items()}
