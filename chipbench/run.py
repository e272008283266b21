#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json `workloads`) names a configuration and a
traffic mix, found by name under chipbench/; the configuration's
model_type names its program side (chipbench/programs/). One process
does all of it: it makes the weights from the seed on the device,
coded into the served w3 format and placed on the cell's chips (a mesh
over the first `chips` devices, as the configuration's serve.mesh
states, when there are several), builds the paged ServeEngine on them,
compiles every shape the mix reaches, offers the traffic for its ramp,
then measures for --seconds. After the window it reads the peak
memory of the fullest of the cell's devices, frees the program's state
and scores a sample of the finished requests with the plain float32
reference. The last line of stdout is one JSON object; with --trace 0
its metrics are the cell's end-to-end metrics, with --trace 1 its
per-layer metrics, read from a profiler trace of the first TRACE_S
seconds of the window (the planes of the cell's devices) and from the
program's own counters.

CHIPBENCH_KEEP_TRACE=<dir> keeps a copy of the traced run's profile
there, for chipbench/tools/trace_dump.py.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
before doing any work; without the program (src/repro) it exits 3.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace


def _process_start() -> float:
    """Wall time at which this process started."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_PROCESS = _process_start()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"
WORK_DIR = ROOT / "chipbench" / ".work"
TRACE_S = 5.0


def info(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Tracer:
    def __init__(self, path: Path):
        self.path = path

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path))

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def end_to_end(win, mix) -> dict:
    """The cell's end-to-end numbers, all from the host clock."""
    W0, W1 = win.t0, win.t1
    due = [t for t in win.tracks if W0 <= t.due < W1]
    ttft = [(t.t_first - t.due) * 1e3 for t in due if t.t_tokens]
    itl, n_tok = [], 0
    for t in win.tracks:
        ts = t.t_tokens
        n_tok += sum(1 for x in ts if W0 <= x <= W1)
        itl += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if W0 <= b <= W1]
    return {"ttft_p95_ms": pct(ttft, 95), "itl_p95_ms": pct(itl, 95),
            "itl_mean_ms": sum(itl) / len(itl) if itl else None,
            "tok_s": n_tok / (W1 - W0),
            "n_due": len(due), "n_ttft": len(ttft), "n_itl": len(itl)}


def serve_mesh(config: dict, chips: int):
    """The mesh of a cell on `chips` devices, over the first of them,
    (data, model) as the configuration's serve.mesh states; None for
    one chip."""
    if chips == 1:
        return None
    from repro.launch.mesh import make_serve_mesh
    m = config["serve"].get("mesh")
    if m is None or m["data"] * m["model"] != chips:
        raise ValueError(f"serve.mesh {m} is not {chips} chips")
    return make_serve_mesh(data=m["data"], model=m["model"])


def device_peak(dev) -> int:
    """One device's peak bytes in use so far (0 where the backend does
    not say)."""
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def build(config: dict, mix: dict, seed: int, *, chips: int = 1,
          trace: bool = False, engine_hook=None):
    """Weights from the seed, the engine on the cell's chips, and every
    shape the mix reaches compiled; returns (engine, loop, traffic)."""
    import jax
    from chipbench.lib import spec
    from chipbench.lib.serve_loop import Loop, warm_up
    from chipbench.lib.traffic import Traffic
    from chipbench.lib.weights import dims
    from repro.serve import ServeEngine
    dm = dims(config)
    prog = spec.program_module(config)
    cfg = prog.model_config(config)
    sv = config["serve"]
    mesh = serve_mesh(config, chips)
    t = time.time()
    params = jax.block_until_ready(prog.params(seed, config, mesh))
    info(f"weights made and coded on the device in {time.time() - t:.1f} s")
    eng = ServeEngine(cfg, params, batch_size=mix["slots"],
                      max_len=mix["max_len"], cache_kind="paged",
                      n_pages=mix.get("pages"),
                      page_size=sv["page_size"], kv_bits=sv["kv_bits"],
                      prefix_sharing=sv["prefix_sharing"],
                      prefill_chunk=sv["prefill_chunk"],
                      speculate=sv["speculate"], mesh=mesh)
    if engine_hook is not None:
        engine_hook(eng)
    loop = Loop(eng, annotate=trace)
    traffic = Traffic(mix, dm["vocab"], seed)
    t = time.time()
    n_warm = warm_up(loop, traffic, mix["max_len"], sv["page_size"])
    info(f"warmed {n_warm} request shapes in {time.time() - t:.1f} s")
    return eng, loop, traffic


def enable_cache():
    import jax
    from repro.serve.compile_cache import enable_persistent_cache
    info(f"compile cache {enable_persistent_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def count_compiles() -> list:
    """(time, event) of every compile from here on."""
    import jax
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: seen.append((time.time(), ev))
        if "compile" in ev and "cache" not in ev else None)
    return seen


def score_sample(config: dict, seed: int, picked, *, control=False):
    """Reference scores of the sampled requests (the program's state
    must be freed before this is called)."""
    from chipbench.lib import check, spec
    ref = spec.reference_module(config)
    t = time.time()
    scores = ref.score(config, seed, check.rows(picked), control=control)
    n = sum(len(s["best"]) for s in scores)
    info(f"reference scored {len(picked)} requests, {n} served tokens, "
         f"in {time.time() - t:.1f} s")
    return scores, n


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, config: dict | None = None,
             mix: dict | None = None, limits: dict | None = None,
             engine_hook=None) -> dict:
    """One run of one cell; returns the result object. `config`, `mix`
    and `limits` replace the cell's files (tests at a small size);
    `engine_hook(engine)` may wrap the engine's calls (tests that break
    the timed path)."""
    import jax
    from chipbench.lib import check, spec
    from chipbench.lib.roofline import peaks as peak_table
    from chipbench.lib.serve_loop import drive
    from chipbench.lib.weights import dims

    bench = bench or spec.load_benchmark()
    cell = spec.cell(cell_name, bench)
    config = config or spec.load_config(cell["config"], bench)
    mix = mix or spec.load_traffic(cell["traffic"])
    limits = limits or check.limits(cell_name)
    enable_cache()
    devs = jax.devices()
    dev, used = devs[0], devs[:cell["chips"]]
    compiles = count_compiles()
    eng, loop, traffic = build(config, mix, seed, chips=cell["chips"],
                               trace=trace, engine_hook=engine_hook)
    tracer = Tracer(WORK_DIR / "trace") if trace else None
    win = drive(loop, traffic, seconds, trace_s=TRACE_S, tracer=tracer)
    setup_s = win.t0 - T_PROCESS
    e2e = end_to_end(win, mix)
    in_window = [ev for ts, ev in compiles if win.t0 <= ts <= win.t1]
    info(f"window {win.t1 - win.t0:.2f} s: {e2e['n_due']} requests due, "
         f"{e2e['n_itl']} token gaps, {len(in_window)} compiles inside")
    mem_peak = max(device_peak(d) for d in used)

    reduced = None
    if trace:
        from chipbench.lib.trace import find_xplane, reduce
        if os.environ.get("CHIPBENCH_KEEP_TRACE"):
            shutil.copytree(WORK_DIR / "trace",
                            os.environ["CHIPBENCH_KEEP_TRACE"],
                            dirs_exist_ok=True)
        reduced = reduce(find_xplane(str(WORK_DIR / "trace")),
                         devices=[d.id for d in used])
        shutil.rmtree(WORK_DIR / "trace", ignore_errors=True)

    run = SimpleNamespace(window=win, loop=loop, trace=reduced,
                          dm=dims(config), mix=mix, config=config,
                          chips=len(used),
                          peaks=peak_table(dev.device_kind)
                          if dev.platform == "tpu" else None)
    per_layer = {}
    for m in spec.cell_metrics(cell_name, "per_layer", bench):
        try:
            v = spec.metric_reader(m["name"]).read(run)
        except Exception as e:  # noqa: BLE001 -- named below, run goes on
            info(f"WARNING: per-layer metric {m['name']} failed: {e!r}")
            v = None
        if v is None:
            if trace:
                info(f"WARNING: per-layer metric {m['name']} read nothing "
                     f"in this run; it is left out of the line")
            continue
        per_layer[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # correctness: the program's state goes first, then the reference
    due = [t for t in win.tracks if win.t0 <= t.due < win.t1]
    unanswered = sum(1 for t in due if not t.t_tokens)
    picked = check.sample(win.tracks, mix["check_requests"], seed)
    wrong = check.wrong_lengths(win.tracks)
    del eng, loop, run
    gc.collect()
    scores, n_scored = score_sample(config, seed, picked)
    numbers = check.gap_numbers(scores)
    info("gaps " + " ".join(f"{k} {v}" for k, v in numbers.items()))
    correct, checks = check.verdict(numbers, wrong, unanswered, n_scored,
                                    limits)

    metrics = {}
    if trace:
        metrics = per_layer
    else:
        vals = {**e2e, "setup_s": setup_s}
        for m in spec.cell_metrics(cell_name, "end_to_end", bench):
            if vals.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(vals[m["name"]]),
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": unanswered, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["window_compiles"] = len(in_window)
    result["checks"] = checks
    for k, c in checks.items():
        info(f"check {k} {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime's logs stay in the checkout, not in /tmp
    os.environ.setdefault("TPU_LOG_DIR", str(WORK_DIR / "tpu_logs"))
    try:
        import repro.serve  # noqa: F401  the system under test
    except ImportError as e:
        print(f"chipbench: the program is not here ({e})", file=sys.stderr)
        return 3
    from chipbench.lib import spec
    bench = spec.load_benchmark()
    cell = spec.cell(args.workload, bench)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX sees {len(devs)} {devs[0].platform} "
              f"device(s). Nothing was run.", file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), bench=bench)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
