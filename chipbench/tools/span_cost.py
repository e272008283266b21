#!/usr/bin/env python3
"""Cost of one serving-engine span (src/repro/serve/trace.py) on this
host: ns per `Recorder.span` entered and left, with the profiler off and
then on, and the same for a bare `TraceAnnotation` and for the empty
loop. The last line is one JSON object.

    python chipbench/tools/span_cost.py [--n N] [--trace-dir DIR]
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src")]


def _ns_per(body, n):
    t = time.perf_counter_ns()
    body(n)
    return (time.perf_counter_ns() - t) / n


def measure(n: int) -> dict:
    from jax.profiler import TraceAnnotation
    from repro.serve.trace import Recorder
    rec = Recorder()

    def spans(k):
        for _ in range(k):
            with rec.span("serve.decode.step", 1):
                pass

    def annotations(k):
        for _ in range(k):
            with TraceAnnotation("serve.decode.step"):
                pass

    def empty(k):
        for _ in range(k):
            pass

    spans(n)                      # fill the ring, warm the key cache
    return {"span_ns": min(_ns_per(spans, n) for _ in range(3)),
            "annotation_ns": min(_ns_per(annotations, n) for _ in range(3)),
            "loop_ns": min(_ns_per(empty, n) for _ in range(3))}


def main(argv=None):
    import jax
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args(argv)
    out = {"device": jax.devices()[0].device_kind, "n": args.n,
           "profiler_off": measure(args.n)}
    d = args.trace_dir or tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        out["profiler_on"] = measure(args.n)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
