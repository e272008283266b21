#!/usr/bin/env python3
"""Print the structure of a profiler trace: planes, lines, and for each
distinct event name on the TPU planes its count, total time and the
stats of its first occurrence. For looking at a trace by hand before
changing the reduction in lib/trace.py.

    python chipbench/tools/trace_dump.py <dir with plugins/profile/...>
"""
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(path):
    from jax.profiler import ProfileData
    from chipbench.lib.trace import _stats, find_xplane
    if not path.endswith(".pb"):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: " + ", ".join(
            f"{l.name}({sum(1 for _ in l.events)})" for l in lines))
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in lines:
            agg = defaultdict(lambda: [0, 0.0, None])
            for ev in line.events:
                a = agg[ev.name]
                a[0] += 1
                a[1] += ev.duration_ns
                if a[2] is None:
                    a[2] = _stats(ev)
            print(f"  LINE {line.name}")
            for name, (n, t, st) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1])[:40]:
                print(f"    {name!r} n={n} total_ms={t / 1e6:.3f}")
                for k, v in (st or {}).items():
                    print(f"        {k}: {str(v)[:600]}")


if __name__ == "__main__":
    main(sys.argv[1])
