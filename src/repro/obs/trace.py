"""Span tracing — ring-buffered events exported as Chrome trace JSON.

Zero-dependency (stdlib only): the spawned PS shard workers import this
through :mod:`repro.ps.server`'s numpy-only path.  Events follow the
Chrome trace-event format (load the exported file in Perfetto /
``chrome://tracing``):

* :func:`span` — a ``with``-scoped complete event (``ph="X"``) carrying
  wall duration, pid/tid lanes and arbitrary JSON-safe args;
* :func:`instant` — a zero-duration marker (``ph="i"``) for lifecycle
  events (fleet join/kill/recover, evictions);
* :class:`TraceBuffer` — bounded ring of event dicts.  The process-global
  :data:`BUFFER` backs the main timeline; a PS shard server keeps its
  *own* buffer and ships it back over the transport's ``obs`` op
  (:meth:`repro.ps.transport.Transport.collect_obs`), where the events —
  stamped with the worker's pid at record time — merge into the global
  buffer as distinct process lanes.

Timestamps are ``time.perf_counter_ns()`` microseconds.  On Linux that
clock is CLOCK_MONOTONIC, which is system-wide: events recorded in
different processes on one machine share a timeline, so the merged trace
needs no cross-process clock alignment (per-lane monotonicity is pinned
in ``tests/test_obs.py``).

Enabled state mirrors :mod:`repro.obs.metrics`: off by default, flipped
by ``repro.obs.configure`` (which also sets ``REPRO_OBS`` so workers
spawned afterwards inherit it).

A span has a second sink: while a JAX profiler session records, it also
opens a profiler ``TraceMe`` of the same name, whether or not the ring
is on, so the program's host spans land in the profile on the device
ops' clock.  The profiler is taken only once jax has loaded it
(``jax._src.lib`` in ``sys.modules``); this module never imports it.
With both sinks off, :func:`span` returns a shared no-op context
manager — one branch and no allocation beyond the profiler's check.

Python's collector gets one ``python.gc`` span per collection, with its
generation as an arg, from a ``gc.callbacks`` hook installed at import.
It goes to the profile alone, never to the ring: a collection may start
while its own thread holds the ring's lock.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
from collections import deque

from repro.obs import metrics as _metrics
from repro.obs import scopes

#: default ring capacity — bounds trace memory on long runs (oldest
#: events fall off; a serve/train session keeps the recent window)
DEFAULT_CAPACITY = 65536

_enabled = _metrics.env_enabled()


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool) -> None:
    global _enabled
    _enabled = bool(flag)


def now_us() -> float:
    return time.perf_counter_ns() / 1e3


#: the profiler's ``TraceMe`` class, once jax is loaded
_TraceMe = None


def profiling() -> bool:
    """Whether a JAX profiler session is recording.  False in a process
    that has not loaded jax; never imports anything, so the collector's
    hook may call it in the middle of an import."""
    global _TraceMe
    if _TraceMe is None:
        profiler = getattr(sys.modules.get("jax._src.lib"), "_profiler",
                           None)
        if profiler is None:
            return False
        _TraceMe = profiler.TraceMe
    return _TraceMe.is_enabled()


class TraceBuffer:
    """Bounded, thread-safe ring of Chrome trace-event dicts."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._events: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def add(self, event: dict) -> None:
        with self._lock:
            self._events.append(event)

    def extend(self, events) -> None:
        with self._lock:
            self._events.extend(events)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def drain(self) -> list[dict]:
        with self._lock:
            out = list(self._events)
            self._events.clear()
        return out

    def __len__(self) -> int:
        return len(self._events)


#: the process-global timeline every un-buffered span lands in
BUFFER = TraceBuffer()


class _NoopSpan:
    __slots__ = ()

    @property
    def args(self):
        # fresh throwaway dict so call sites can annotate span args
        # (`sp.args["dropped"] = n`) without checking the enabled switch
        return {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    """Records into ``buf`` (None: the ring is off) and, while the
    profiler records, into the profile."""

    __slots__ = ("name", "cat", "args", "buf", "t0", "tm")

    def __init__(self, name, cat, buf, args):
        self.name = name
        self.cat = cat
        self.buf = buf
        self.args = args

    def __enter__(self):
        self.tm = _TraceMe(self.name, **self.args) if profiling() else None
        if self.tm is not None:
            self.tm.__enter__()
        self.t0 = now_us()
        return self

    def __exit__(self, *exc):
        t1 = now_us()
        if self.tm is not None:
            self.tm.__exit__(*exc)
        if self.buf is not None:
            self.buf.add({
                "name": self.name, "cat": self.cat, "ph": "X",
                "ts": self.t0, "dur": t1 - self.t0,
                "pid": os.getpid(), "tid": threading.get_native_id(),
                "args": self.args,
            })
        return False


def span(name: str, cat: str = "repro", *, buffer: TraceBuffer | None = None,
         **args):
    """``with span("serve.prefill", rid=3): ...`` — records a complete
    event on exit.  Near-free when disabled and no profiler records
    (shared no-op object)."""
    if _enabled:
        return _Span(name, cat, buffer if buffer is not None else BUFFER,
                     args)
    if profiling():
        return _Span(name, cat, None, args)
    return _NOOP


_gc_span = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``python.gc`` span per collection, in
    the profile only.  A collection can run while this thread holds the
    ring's lock, so the hook never records into the ring."""
    global _gc_span
    if phase == "start":
        if profiling():
            _gc_span = _Span(scopes.PYTHON_GC, "python", None,
                             {"generation": info["generation"]})
            _gc_span.__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)


def instant(name: str, cat: str = "repro", *,
            buffer: TraceBuffer | None = None, **args) -> None:
    """Zero-duration marker (lifecycle events)."""
    if not _enabled:
        return
    (buffer if buffer is not None else BUFFER).add({
        "name": name, "cat": cat, "ph": "i", "s": "p",
        "ts": now_us(),
        "pid": os.getpid(), "tid": threading.get_native_id(),
        "args": args,
    })


def label_process(name: str, *, buffer: TraceBuffer | None = None) -> None:
    """Name this process's pid lane in the merged trace (``ph="M"``)."""
    (buffer if buffer is not None else BUFFER).add({
        "name": "process_name", "ph": "M", "ts": 0,
        "pid": os.getpid(), "tid": threading.get_native_id(),
        "args": {"name": name},
    })


def merged(*event_lists) -> list[dict]:
    """Merge event lists onto one timeline: metadata first, then events
    sorted by timestamp — which makes every (pid, tid) lane monotonic."""
    meta, evs = [], []
    for lst in event_lists:
        for e in lst:
            (meta if e.get("ph") == "M" else evs).append(e)
    evs.sort(key=lambda e: e.get("ts", 0.0))
    return meta + evs


def chrome_trace(events: list[dict]) -> dict:
    """Wrap merged events in the Chrome trace-event envelope."""
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome(path: str, *event_lists) -> str:
    """Merge ``event_lists`` (default: the global buffer) and write a
    Perfetto-loadable Chrome trace JSON.  Returns the path."""
    if not event_lists:
        event_lists = (BUFFER.events(),)
    with open(path, "w") as f:
        json.dump(chrome_trace(merged(*event_lists)), f, default=str)
    return path
