"""The serving engine's own spans (src/repro/serve/trace.py), read two
ways.

From the engine's ring of span records, `(name, t0_ns, t1_ns, n)` on
`time.time_ns()`, for the per-layer metrics: `window_records`
gives the records of the untraced part of the window, from the end of
the trace (the window's start, in an untraced run) to the window's
end. Stopping the profiler stalls the loop, so the traced seconds are
left out.

From a profiler trace, where each span is also a host event of its
name: `load` keeps those events beside what `trace.load` keeps, and
`idle_split` attributes each idle gap of the device to the innermost
`serve.*` span open during it, else to the harness phase open during
it, as `trace.reduce_events` does, else to "none". Per phase, what the
phase keeps plus what went to the spans inside it is what
`trace.reduce_events` gives that phase.
"""
from __future__ import annotations

import bisect
import sys
from collections import defaultdict

from chipbench.lib import trace

PREFIX = "serve."


def _warn(msg: str) -> None:
    print(f"[chipbench] WARNING: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the ring

def window_records(run):
    """The engine's span records that lie inside [trace end, window end]
    ([W0, W1] untraced); None where the engine keeps no ring, or where
    the ring no longer reaches back to the start."""
    rec = getattr(run.loop.eng, "recorder", None)
    if rec is None:
        return None
    w = run.window
    lo = int((w.trace[1] if w.trace else w.t0) * 1e9)
    hi = int(w.t1 * 1e9)
    ring = list(rec.ring)
    if not ring or ring[0][1] > lo:
        _warn(f"the engine's span ring starts after {lo} ns, so it does "
              f"not cover the window; its metrics are not read")
        return None
    return [r for r in ring if lo <= r[1] and r[2] <= hi]


def named(records, name):
    """Records of one span name, by start time."""
    return sorted((r for r in records if r[0] == name), key=lambda r: r[1])


def host_ms_per_tick(records):
    """Mean over decode ticks that waited on a decode step of the tick's
    duration minus that wait, in ms."""
    waits = named(records, "serve.decode.wait")
    starts = [w[1] for w in waits]
    host = []
    for _, t0, t1, _ in named(records, "serve.decode.tick"):
        i = bisect.bisect_left(starts, t0)
        inside = [w for w in waits[i:bisect.bisect_right(starts, t1)]
                  if w[2] <= t1]
        if inside:
            host.append((t1 - t0) - sum(w[2] - w[1] for w in inside))
    return sum(host) / len(host) / 1e6 if host else None


def tick_gaps_ms(records):
    """For each decode step's wait, the time from its end to the start
    of the next decode step's dispatch, in ms."""
    waits = named(records, "serve.decode.wait")
    starts = [d[1] for d in named(records, "serve.decode.dispatch")]
    gaps = []
    for w in waits:
        i = bisect.bisect_left(starts, w[2])
        if i < len(starts):
            gaps.append((starts[i] - w[2]) / 1e6)
    return gaps


# ----------------------------------------------------------- the trace

def load(path: str) -> dict:
    """`trace.load(path)`, plus 'spans': the host events whose name
    starts with `serve.`, as (name, start, end) in the trace's ns."""
    from jax.profiler import ProfileData
    tr = trace.load(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = float(ev.start_ns)
                    spans.append((ev.name, s, s + float(ev.duration_ns)))
    tr["spans"] = spans
    return tr


def nest(spans):
    """[(name, start, end, parent index or None)] by start, for spans
    that nest (one thread's annotations)."""
    out, stack = [], []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            e = min(e, out[parent][2])
        out.append((name, s, e, parent))
        stack.append(len(out) - 1)
    return out


def innermost(spans):
    """Disjoint (start, end, name) pieces of the time some span is open,
    each named for the innermost span open in it."""
    nested = nest(spans)
    pieces, stack, pos = [], [], None

    def close_until(t):
        nonlocal pos
        while stack and nested[stack[-1]][2] <= t:
            top = nested[stack.pop()]
            if top[2] > pos:
                pieces.append((pos, top[2], top[0]))
            pos = top[2]

    for i, (name, s, e, _) in enumerate(nested):
        close_until(s)
        if stack and s > pos:
            pieces.append((pos, s, nested[stack[-1]][0]))
        pos = s
        stack.append(i)
    close_until(float("inf"))
    return pieces


def self_times(spans) -> dict:
    """{name: [count, total ns, self ns]}: self is the duration less
    what the spans directly inside it cover."""
    nested = nest(spans)
    child = [0.0] * len(nested)
    for name, s, e, parent in nested:
        if parent is not None:
            child[parent] += e - s
    out = defaultdict(lambda: [0, 0.0, 0.0])
    for (name, s, e, _), c in zip(nested, child):
        a = out[name]
        a[0] += 1
        a[1] += e - s
        a[2] += e - s - c
    return dict(out)


def idle_split(tr: dict) -> dict:
    """Idle gaps of the device by innermost open program span, else by
    harness phase, else 'none'; per phase its total, what it keeps, and
    what went to each span inside it; and the share of device busy time
    inside some program span. Seconds, averaged over TPU planes, as
    `trace.reduce_events` reports them."""
    wins = [h for h in tr["host"] if h[0] == trace.WINDOW]
    if not wins or not tr["device"]:
        raise ValueError("trace has no trace_window span or no TPU plane")
    lo, hi = wins[0][1], wins[0][2]
    phases = [h for h in tr["host"] if h[0] != trace.WINDOW
              and h[2] > lo and h[1] < hi]
    spans = tr.get("spans", [])
    pieces = innermost(spans)
    span_iv = _complement(
        trace.union([(s, e) for _, s, e in spans], lo, hi)[1], lo, hi)
    total = defaultdict(float)                 # phase -> old total
    rest = defaultdict(float)                  # phase -> what it keeps
    under = defaultdict(lambda: defaultdict(float))  # phase -> span -> s
    busy_all, busy_in = [], []

    def to_phases(a, b, span=None):
        """Idle [a, b] to the phases open in it, or to `span` under
        them."""
        covered = 0.0
        for name, s, t in phases:
            o = min(b, t) - max(a, s)
            if o > 0:
                covered += o
                total[name] += o
                if span is None:
                    rest[name] += o
                else:
                    under[name][span] += o
        o = max(0.0, (b - a) - covered)
        total["none"] += o
        if span is None:
            rest["none"] += o
        else:
            under["none"][span] += o

    for evs in tr["device"].values():
        inside = [(e[1], e[2]) for e in evs if lo <= e[1] and e[2] <= hi]
        busy, gaps = trace.union(inside, lo, hi)
        busy_all.append(busy)
        busy_in.append(_intersect(_complement(gaps, lo, hi), span_iv))
        j = 0
        for a, b in gaps:
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            pos, k = a, j
            while k < len(pieces) and pieces[k][0] < b:
                x, y, name = pieces[k]
                x, y = max(x, a), min(y, b)
                if x > pos:
                    to_phases(pos, x)
                to_phases(x, y, span=name)
                pos = y
                k += 1
            if b > pos:
                to_phases(pos, b)
    n = len(busy_all)
    by_span = defaultdict(float)
    for ph in under.values():
        for span, v in ph.items():
            by_span[span] += v
    gaps = [[k, v / 1e9 / n] for k, v in by_span.items()] + \
        [[k, v / 1e9 / n] for k, v in rest.items()]
    busy = sum(busy_all)
    return {
        "idle_gaps": sorted(gaps, key=lambda kv: -kv[1]),
        "by_phase": {k: {"total": total[k] / 1e9 / n,
                         "keeps": rest[k] / 1e9 / n,
                         "spans": {s: v / 1e9 / n
                                   for s, v in under[k].items()}}
                     for k in total},
        "busy_in_spans": sum(busy_in) / busy if busy else None,
    }


def _complement(gaps, lo, hi):
    """The intervals of [lo, hi] outside `gaps` (sorted, disjoint)."""
    out, pos = [], lo
    for a, b in gaps:
        if a > pos:
            out.append((pos, a))
        pos = b
    if hi > pos:
        out.append((pos, hi))
    return out


def _intersect(p, q):
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = 0
    out = 0.0
    while i < len(p) and j < len(q):
        o = min(p[i][1], q[j][1]) - max(p[i][0], q[j][0])
        if o > 0:
            out += o
        if p[i][1] < q[j][1]:
            i += 1
        else:
            j += 1
    return out
