#!/usr/bin/env python3
"""Readings that set a cell's limits and rates; not part of a run.

    python chipbench/calibrate.py gaps --workload <cell> --seeds 1,2,3 \
        [--control 3] [--witness 3] [--seconds 15]
    python chipbench/calibrate.py knee --workload <cell> \
        --rates 8,16,24 [--seconds 20]

`gaps` runs the cell on each seed in one process (compiling once), at
the cell's own traffic for a short window, and scores a sample of the
finished requests as a run does: how far the served tokens' logits lie
below the reference's best (lib/check.py:gap_numbers). On the first --control seeds it also
runs the control -- the reference with every matmul operand and the
residual stream rounded to float8 e4m3 -- and reads the same numbers
for the token the control ranks first. The program's readings set the lower end of the limit, the
control's the upper end (chipbench/cells/<cell>.json); both are judged
by the run's own verdict (lib/check.py) against the cell's limit as it
stands, and the control's `control_correct` has to come out false.
On the first --witness seeds it runs the reference once more with the
same tensors rounded to bf16, the program's own precision, and reads
the same numbers for the token that witness ranks first: what
rounding alone gives. Each line also names the widest gaps (request, prompt length,
output offset, gap) and the share of positions where the served token
is not the reference's first.

`knee` offers an open-loop cell's mix at each rate in turn (one engine,
drained between rates) and prints, per rate, the tails and the backlog:
the requests due in the window that were not admitted by its close.

Each reading is one JSON line on stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent)]
import run as R  # noqa: E402  (sets sys.path for the program)


def where(scores, picked, key="served", k=5):
    """The k widest gaps as [request, prompt length, output offset,
    gap], and the share of positions with a gap above 0."""
    import numpy as np
    rows, n_pos, n_off = [], 0, 0
    for r, (s, t) in enumerate(zip(scores, picked)):
        g = s["best"] - s[key]
        n_pos += len(g)
        n_off += int(np.sum(g > 0))
        rows += [[r, len(t.prompt), int(j), float(g[j])]
                 for j in np.argsort(-g)[:k]]
    rows.sort(key=lambda x: -x[3])
    return rows[:k], n_off / max(n_pos, 1)


def dump(path, seed, picked, scores, control=None, witness=None):
    """Every scored position of one seed to <path>/<seed>.npz: the
    reference's lead of its best logit over its second, the gap below
    its best of the served token, of the control's first and of the
    witness's first (each from its own reference pass), and each
    position's request, prompt length and output offset."""
    import numpy as np
    cols = {k: [] for k in ("req", "prompt_len", "offset", "lead",
                            "served", "ctrl", "witness")}
    for r, (s, t) in enumerate(zip(scores, picked)):
        n = len(s["best"])
        cols["req"].append(np.full(n, r))
        cols["prompt_len"].append(np.full(n, len(t.prompt)))
        cols["offset"].append(np.arange(n))
        cols["lead"].append(s["best"] - s["second"])
        cols["served"].append(s["best"] - s["served"])
        for k, sc in (("ctrl", control), ("witness", witness)):
            cols[k].append(sc[r]["best"] - sc[r]["ctrl"] if sc
                           else np.zeros(n))
    Path(path).mkdir(parents=True, exist_ok=True)
    np.savez(Path(path) / f"{seed}.npz",
             **{k: np.concatenate(v) for k, v in cols.items()})


def gaps(cell_name, seeds, n_control, n_witness, seconds, dump_dir):
    from chipbench.lib import check, spec
    from chipbench.lib.serve_loop import drive
    bench = spec.load_benchmark()
    cell = spec.cell(cell_name, bench)
    config = spec.load_config(cell["config"], bench)
    mix = spec.load_traffic(cell["traffic"])
    limits = check.limits(cell_name)
    for i, seed in enumerate(seeds):
        t0 = time.time()
        eng, loop, traffic = R.build(config, mix, seed,
                                     chips=cell["chips"])
        win = drive(loop, traffic, seconds)
        e2e = R.end_to_end(win, mix)
        picked = check.sample(win.tracks, mix["check_requests"], seed)
        wrong = check.wrong_lengths(win.tracks)
        unanswered = sum(1 for t in win.tracks
                         if win.t0 <= t.due < win.t1 and not t.t_tokens)
        del eng, loop
        gc.collect()
        control = i < n_control
        scores, n = R.score_sample(config, seed, picked, control=control)
        prog = check.gap_numbers(scores)
        ok, _ = check.verdict(prog, wrong, unanswered, n, limits)
        out = {"cell": cell_name, "seed": seed, "program": prog,
               "correct": ok, "scored_tokens": n, "wrong_lengths": wrong,
               "unanswered": unanswered,
               "longest_out": max(len(t.req.out) for t in picked),
               "ttft_p95_ms": e2e["ttft_p95_ms"],
               "itl_p95_ms": e2e["itl_p95_ms"],
               "itl_mean_ms": e2e["itl_mean_ms"], "tok_s": e2e["tok_s"],
               "wall_s": time.time() - t0}
        out["top"], out["off_share"] = where(scores, picked)
        if control:
            # the control in the program's place, judged by the same
            # verdict and the cell's limits: it has to come out false
            ctrl = check.gap_numbers(scores, "ctrl")
            out["control"] = ctrl
            out["control_correct"], _ = check.verdict(
                ctrl, wrong, unanswered, n, limits)
            out["control_top"], out["control_off_share"] = where(
                scores, picked, "ctrl")
        ws = None
        if i < n_witness:
            ws, _ = R.score_sample(config, seed, picked, control="bf16")
            out["witness"] = check.gap_numbers(ws, "ctrl")
            out["witness_top"], out["witness_off_share"] = where(
                ws, picked, "ctrl")
        if dump_dir:
            dump(dump_dir, seed, picked, scores,
                 scores if control else None, ws)
        print(json.dumps(out), flush=True)


def knee(cell_name, rates, seconds, seed):
    import numpy as np
    from chipbench.lib import spec
    from chipbench.lib.serve_loop import drive
    from chipbench.lib.traffic import Traffic
    bench = spec.load_benchmark()
    cell = spec.cell(cell_name, bench)
    config = spec.load_config(cell["config"], bench)
    mix = spec.load_traffic(cell["traffic"])
    eng, loop, warm = R.build(config, mix, seed, chips=cell["chips"])
    for i, rate in enumerate(rates):
        m = dict(mix, rate=rate)
        t = Traffic(m, warm.vocab, seed + 1 + i)
        t.prefixes = warm.prefixes
        t.first_base = (warm.first_base + 20000 * (i + 1)) % warm.vocab
        w = drive(loop, t, seconds)
        e = R.end_to_end(w, m)
        due = [x for x in w.tracks if w.t0 <= x.due < w.t1]
        backlog = sum(1 for x in due if not x.t_admit or x.t_admit > w.t1)
        waits = [(x.t_admit - x.due) * 1e3 for x in due if x.t_admit]
        ttft = [(x.t_first - x.due) * 1e3 for x in due if x.t_tokens]
        loop.run_until_idle()
        print(json.dumps({
            "cell": cell_name, "rate": rate, "due": len(due),
            "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
            "ttft_p95_ms": e["ttft_p95_ms"], "itl_p95_ms": e["itl_p95_ms"],
            "itl_mean_ms": e["itl_mean_ms"],
            "queue_wait_p50_ms": float(np.median(waits)) if waits else None,
            "backlog_at_close": backlog, "tok_s": e["tok_s"]}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("gaps", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--dump", default="",
                    help="directory for each seed's scored positions")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE_DIR)
    import jax
    from chipbench.lib import spec
    devs = jax.devices()
    if devs[0].platform != "tpu" or \
            len(devs) < spec.cell(args.workload)["chips"]:
        print("calibrate: not enough TPU chips; nothing was run",
              file=sys.stderr)
        return 2
    R.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mode == "gaps":
        gaps(args.workload, seeds, args.control, args.witness,
             args.seconds, args.dump)
    else:
        knee(args.workload, [float(r) for r in args.rates.split(",")],
             args.seconds, seeds[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
