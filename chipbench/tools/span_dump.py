#!/usr/bin/env python3
"""Print the serving engine's spans in a profiler trace: each `serve.*`
span's count, total and self time inside the trace window; the device's
idle time by the innermost span open in it, else by harness phase
(lib/spans.py:idle_split), each phase's total beside what
lib/trace.py:reduce_events gives it; and the share of the device's busy
time that falls inside some span. The last line is all of it as one
JSON object.

    python chipbench/tools/span_dump.py <dir with plugins/profile/...>
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def summary(path: str) -> dict:
    from chipbench.lib import spans, trace
    if not path.endswith(".pb"):
        path = trace.find_xplane(path)
    tr = spans.load(path)
    lo, hi = next(h[1:] for h in tr["host"] if h[0] == trace.WINDOW)
    inside = [s for s in tr["spans"] if lo <= s[1] and s[2] <= hi]
    split = spans.idle_split(tr)
    old = dict(trace.reduce_events(tr)["breakdown"]["idle_gaps"])
    for phase, v in split["by_phase"].items():
        v["reduce_events"] = old.get(phase)
    return {"window_s": (hi - lo) / 1e9,
            "spans_ms": {k: {"n": n, "total": t / 1e6, "self": s / 1e6}
                         for k, (n, t, s) in spans.self_times(inside).items()},
            **split}


def main(path):
    out = summary(path)
    print(f"window {out['window_s']:.3f} s; device busy time inside "
          f"serve.* spans: {out['busy_in_spans']}")
    print("span                            n   total_ms    self_ms")
    for k, v in sorted(out["spans_ms"].items(), key=lambda kv: -kv[1]["self"]):
        print(f"{k:28s} {v['n']:5d} {v['total']:10.3f} {v['self']:10.3f}")
    print("idle (s), by innermost span, else phase")
    for k, v in out["idle_gaps"]:
        print(f"  {k:32s} {v:.6f}")
    print("per phase: total = keeps + spans (reduce_events' total)")
    for k, v in out["by_phase"].items():
        print(f"  {k:18s} {v['total']:.6f} = {v['keeps']:.6f} + "
              f"{sum(v['spans'].values()):.6f} ({v['reduce_events']})")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
