"""Spans and counters of the serving engine.

One `Recorder` per `ServeEngine`, shared with its `Scheduler`. A span
marks one stretch of host work at a layer boundary (one admission, one
decode tick and its parts) and is written twice, at the same two
instants:

  - into the profiler's trace, as a `jax.profiler.TraceAnnotation` of
    the span's name: the host line of a trace shows it on the clock the
    device's ops are stamped with, so a reader can say what the engine
    was doing while the device sat idle;
  - into a bounded in-memory ring of `(name, t0_ns, t1_ns, n)` records,
    stamped with `time.time_ns()` (the clock of `RequestMetrics` and of
    the benchmark's due times), where `n` is one integer the span
    carries: the request id of an admission, the rows of a decode step.
    Spans of one engine nest, so a span's children are the records
    that lie inside it in time.

Each name's total lands in the engine's `stats` dict under the flat key
`<name>_s` (seconds); a span may also feed one of the engine's
long-standing accumulators (`decode_s`, `prefill_s`), which are then
exactly its total. `count(name, k)` adds to a flat counter in the same
dict.

A span reads the host clock and nothing else: it never waits for the
device. Names start with `serve.`; docs/SERVING.md lists them.
"""
from __future__ import annotations

import time
from collections import deque

from jax.profiler import TraceAnnotation

RING = 65536


class Recorder:
    """Spans and counters written into `stats` (the engine's dict) and
    into a ring of the last `maxlen` span records."""

    def __init__(self, stats: dict | None = None, maxlen: int = RING):
        self.stats = {} if stats is None else stats
        self.ring: deque = deque(maxlen=maxlen)
        self._keys: dict[str, str] = {}

    def span(self, name: str, n: int = 0, feeds: str | None = None):
        """Context manager timing one stretch of host work; `feeds`
        names a stats key the duration is also added to."""
        return _Span(self, name, n, feeds)

    def count(self, name: str, k: int) -> None:
        self.stats[name] = self.stats.get(name, 0) + k

    def _close(self, name, t0, t1, n, feeds) -> None:
        self.ring.append((name, t0, t1, n))
        key = self._keys.get(name)
        if key is None:
            key = self._keys[name] = name + "_s"
        dt = (t1 - t0) / 1e9
        st = self.stats
        st[key] = st.get(key, 0.0) + dt
        if feeds is not None:
            st[feeds] = st.get(feeds, 0.0) + dt


class _Span:
    __slots__ = ("rec", "name", "n", "feeds", "ann", "t0")

    def __init__(self, rec, name, n, feeds):
        self.rec, self.name, self.n, self.feeds = rec, name, n, feeds

    def __enter__(self):
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self.ann.__exit__(*exc)
        self.rec._close(self.name, self.t0, t1, self.n, self.feeds)
        return False
