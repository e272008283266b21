"""Reduce a profiler trace (the .xplane.pb that jax.profiler writes) to
device busy time, idle gaps by host phase, and per-kernel device time.

Idle time is split over the host phases that were open during it (the
adapter's phases do not overlap); what no phase covers is "none".

Device work is read from the "XLA Ops" line of the TPU planes of the
devices the run used: a host may write a plane for each of its chips,
and busy time and the breakdown are averaged over the used ones only.
The host phases are the TraceAnnotation spans the serving-loop adapter
writes (serve_loop.PHASES) and the "trace_window" span around the
traced iterations, which sets the window. Busy time is the union of op
intervals inside the window, so overlapping ops count once.

Kernels are told apart by the HLO of the custom call: a Pallas call
whose operands include packed uint32 code planes is a BCQ GEMM -- the
batched-expert one (`bcq_expert`) where x is (E, M, K) and the codes
(E, bits, K/32, N); one whose operands are an int32 block table,
context lengths and pages is paged attention. On a TPU v5e the
instruction text is the event's name ("%bcq_matmul.68 =
bf16[64,9728]{...} custom-call(...), custom_call_target=..."); its
stats (TEXT_STATS) are read where the name is short. Only the result
and the operands count: the text is cut at `custom_call_target`, after
which the layout constraints repeat the operand types.

A `while` or `conditional` op spans the ops of its body on the same
line: it counts towards busy time (a union), not in the breakdown of
device time by op.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from chipbench.lib.roofline import (codes_index, expert_codes_index,
                                    parse_shapes)

WINDOW = "trace_window"
TEXT_STATS = ("long_name", "hlo_text", "tf_op", "name")
KERNELS = ("bcq", "bcq_expert", "paged_attention")
TPU_PLANE = re.compile(r"/device:TPU:(\d+)")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (AttributeError, TypeError, ValueError):
        return {}


def load(path: str) -> dict:
    """{'device': {plane: [(name, start, end, stats)]},
    'host': [(name, start, end)]} in the trace's nanoseconds."""
    from jax.profiler import ProfileData
    from chipbench.lib.serve_loop import PHASES
    pd = ProfileData.from_file(path)
    device, host = {}, []
    wanted = set(PHASES) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((ev.name, s, s + float(ev.duration_ns),
                                _stats(ev)))
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = float(ev.start_ns)
                        host.append((ev.name, s, s + float(ev.duration_ns)))
    return {"device": device, "host": host}


def union(intervals, lo: float, hi: float):
    """(busy length, idle gaps) of intervals clipped to [lo, hi]."""
    iv = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                if b > lo and a < hi)
    busy, gaps, end = 0.0, [], lo
    for a, b in iv:
        if a > end:
            gaps.append((end, a))
        if b > end:
            busy += b - max(a, end)
            end = b
    if hi > end:
        gaps.append((end, hi))
    return busy, gaps


CONTAINERS = ("while", "conditional", "call")


def op_text(ev) -> str:
    """The HLO instruction text of a device op, up to its
    custom_call_target, or ''."""
    for v in [ev[0]] + [ev[3].get(k) for k in TEXT_STATS]:
        if isinstance(v, str) and "custom-call(" in v:
            return v.split("custom_call_target", 1)[0]
    return ""


def kernel_of(text: str) -> str | None:
    """'bcq', 'bcq_expert' or 'paged_attention' for the Pallas calls of
    this program, from the operand types of the custom call; None
    otherwise."""
    if "tpu_custom_call" not in text and "custom-call" not in text:
        return None
    sh = parse_shapes(text)
    ops = sh[1:]
    if expert_codes_index(sh) is not None:
        return "bcq_expert"
    if codes_index(sh) is not None:
        return "bcq"
    if len(ops) >= 5 and ops[0][0] == "s32" and len(ops[0][1]) == 2 \
            and ops[1][0] == "s32" and len(ops[1][1]) == 1:
        return "paged_attention"
    return None


def _short(name: str) -> str:
    """'%bcq_matmul.68 = bf16[...] custom-call(...)' -> 'bcq_matmul'."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def reduce(path: str, devices=None) -> dict:
    """Window, busy time, kernels and breakdown of one trace file, over
    the planes of `devices` (ids; every TPU plane when None)."""
    return reduce_events(load(path), devices)


def used_planes(tr: dict, devices=None) -> dict:
    """The device planes of `tr` whose TPU id is among `devices`."""
    if devices is None:
        return tr["device"]
    ids = {str(d) for d in devices}
    return {name: evs for name, evs in tr["device"].items()
            if (m := TPU_PLANE.match(name)) and m.group(1) in ids}


def reduce_events(tr: dict, devices=None) -> dict:
    """Window, busy time, kernels and breakdown of loaded events
    (`load`'s form), over the planes of `devices` (ids; all when
    None)."""
    wins = [h for h in tr["host"] if h[0] == WINDOW]
    planes = used_planes(tr, devices)
    if not wins or not planes:
        raise ValueError(f"trace has no trace_window span or no plane of "
                         f"TPU {devices}: {sorted(tr['device'])}")
    lo, hi = wins[0][1], wins[0][2]
    phases = [h for h in tr["host"] if h[0] != WINDOW
              and h[2] > lo and h[1] < hi]
    busy_all, calls, ops_time = [], [], defaultdict(float)
    gap_by_phase = defaultdict(float)
    for evs in planes.values():
        inside = [e for e in evs if lo <= e[1] and e[2] <= hi]
        busy, gaps = union([(e[1], e[2]) for e in inside], lo, hi)
        busy_all.append(busy)
        for e in inside:
            text = op_text(e)
            k = kernel_of(text)
            dur = e[2] - e[1]
            calls.append((k, dur, text, e[0]))
            short = _short(e[0])
            if short not in CONTAINERS:
                ops_time[k or short] += dur
        for a, b in gaps:
            covered = 0.0
            for name, s, t in phases:
                o = min(b, t) - max(a, s)
                if o > 0:
                    gap_by_phase[name] += o
                    covered += o
            gap_by_phase["none"] += max(0.0, (b - a) - covered)
    n = len(busy_all)
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gap_by_phase.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_all) / n / 1e9,
        "calls": calls,
        "kernel_s": {k: v / 1e9 for k, v in ops_time.items()
                     if k in KERNELS},
        "breakdown": {"device_ops": [[k, v / 1e9 / n] for k, v in top],
                      "idle_gaps": [[k, v / 1e9 / n] for k, v in idle]},
        "span_ns": (lo, hi),
    }
