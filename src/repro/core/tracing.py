"""Host spans and per-step counters of the served path, on the profiler's
clock.

Three parts, always on (with the profiler off a span costs one to two
microseconds):

* :class:`span` — a ``jax.profiler.TraceAnnotation`` that also times its
  interval with ``time.perf_counter``, so a :class:`PathStepStats
  <repro.core.path.PathStepStats>` field and the trace record the same
  interval. Keyword ids (``batch_id=``, ``k=``) land on the trace event.
* :func:`fetch` — a device→host read (``np.asarray``) inside a
  ``path.sync`` span, counted into the current λ step's ``host_syncs`` and
  ``host_sync_s``.
* a ``jax.monitoring`` listener on backend compiles (which also fire for
  persistent-cache loads), counted into the current step's ``compiles``.

The current step is thread-local: :class:`step` opens it around one λ step
of ``_path_driver``, which copies its counters into the step's statistics.
docs/serving.md#tracing-a-served-path lists every span.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_local = threading.local()


class span:
    """``with span(name, **ids) as s: ...`` — a profiler trace annotation;
    ``s.seconds`` is the interval's length once the block has closed."""

    __slots__ = ("_annotation", "_t0", "seconds")

    def __init__(self, name: str, **ids):
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)
        self.seconds = 0.0

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        return self._annotation.__exit__(*exc)


class step:
    """``with step(k) as st: ...`` — the ``path.step`` span of λ step ``k``,
    with ``st`` the step's counters, current on this thread while it is
    open: ``host_syncs``, ``host_sync_s``, ``compiles``, and once it has
    closed, the span's length ``seconds``."""

    __slots__ = ("k", "host_syncs", "host_sync_s", "compiles", "seconds",
                 "_span", "_prev")

    def __init__(self, k: int):
        self.k = k
        self.host_syncs = 0
        self.host_sync_s = 0.0
        self.compiles = 0
        self.seconds = 0.0
        self._span = span("path.step", k=k)

    def __enter__(self) -> "step":
        self._prev = getattr(_local, "step", None)
        _local.step = self
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        _local.step = self._prev
        self.seconds = self._span.seconds
        return False


def fetch(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)`` as one counted device→host sync."""
    with span("path.sync") as s:
        out = np.asarray(x, dtype=dtype)
    c = getattr(_local, "step", None)
    if c is not None:
        c.host_syncs += 1
        c.host_sync_s += s.seconds
    return out


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    if event == BACKEND_COMPILE:
        c = getattr(_local, "step", None)
        if c is not None:
            c.compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)
