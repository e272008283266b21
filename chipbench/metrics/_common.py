"""Helpers the metric readers share: which admissions and ticks fall in
the traced span, the trace's calls of one kernel, the device's idle
share, and the time per decode step."""
from __future__ import annotations


def in_trace(records, run):
    """Admission or tick records (t0, t1, ...) inside the traced span."""
    lo, hi = run.window.trace
    return [r for r in records if r[0] >= lo and r[1] <= hi]


def calls(run, kind):
    return [c for c in run.trace["calls"] if c[0] == kind]


def idle_share(run):
    """Share of the traced span in which no operation ran on the device
    (1 - union of op intervals / span), in %."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def decode_ms(run):
    """Time per decode step: the engine's own `decode_s` (jitted step
    plus block_until_ready) over its `ticks`, both taken over the
    window."""
    a, b = run.window.stats0, run.window.stats1
    n = b["ticks"] - a["ticks"]
    return 1e3 * (b["decode_s"] - a["decode_s"]) / n if n else None
