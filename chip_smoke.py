#!/usr/bin/env python3
"""Chip smoke test: serve a full-width GPTQT-w3 qwen3-0.6b on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded serving on a 4-chip host

One process does all the work and starts no other. With no option it:
  1. builds qwen3-0.6b at its published widths in bf16, weights drawn
     from --seed (no checkpoint is read);
  2. GPTQT-quantizes it to w3 / group_size 128 / packed, calibrated on
     batches cut from the in-repo synthetic corpus;
  3. serves REQUESTS through the paged engine twice, with raw bf16 KV
     pages and with 4-bit binary-coded pages;
  4. checks that the compiled decode step calls Pallas kernels
     (`tpu_custom_call`);
  5. compares each request's served prefill logits and first decode
     step logits with a plain float32 jax.numpy forward over the
     dequantized weights, under the tolerances below.

With --chips 4 it runs only the sharded path: the same quantized model
and requests served over two 4-device meshes, data=4 (page pool and
batch split four ways) and data=2 x model=2 (tensor-parallel weights
and kv heads as well), each compared on logits with the same requests
served on one device in this process and with the float32 reference.

Lines tagged [info] carry timings and counts; they are informational,
not benchmark numbers. The last line of stdout is one JSON object,
{"ok": true, "device": {...}}, printed only when every phase passed.
Without a TPU the script exits 2 before doing any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

ARCH = "qwen3-0.6b"
BITS, GROUP_SIZE = 3, 128
KV_BITS = 4
PAGE_SIZE, MAX_LEN, BATCH = 64, 256, 4
# four prompts of byte tokens, one prefill bucket (<= 128); 16 new each
PROMPT_LENS = (72, 88, 104, 120)
MAX_NEW = 16
# calibration batches of 4 x 192 corpus tokens (benchmarks/common.py)
CALIB_BATCHES = 6
# relative RMS error ||served - ref|| / ||ref|| of a logit vector.
# Served runs bf16 activations (weights expand to f32 tiles, feed the
# MXU as bf16) against an f32 forward on the same dequantized weights.
TOL_SERVED = 0.05
# 4-bit KV pages add the coding error of every cached K/V vector
TOL_KV4 = 0.25
# mesh vs one device: the same bf16 model, partitioned. Each mesh run
# must also be as close to the float32 reference as one device is
# (TOL_SERVED): the mesh may round bf16 in other places, never be less
# accurate
TOL_MESH = 0.02
# the 4-chip meshes, (data, model)
MESHES = ((4, 1), (2, 2))


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def rel_rms(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def make_engine_class():
    from repro.serve import ServeEngine

    class RecordingEngine(ServeEngine):
        """The paged serving engine, keeping for each request the
        logits of its prefill and of its first decode step (the step
        that feeds the first generated token at position len(prompt)).
        Float32 host copies, keyed by id(request). The engine has no
        public hook for logits: this wraps its jitted decode step
        (`_decode`) and its first-token emission (`_emit_first_token`),
        and breaks if either is renamed."""

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.prefill_logits, self.step_logits = {}, {}
            step = self._decode

            def recording_step(*step_args):
                import numpy as np
                logits, cache = step(*step_args)
                for slot, e in self.sched.running.items():
                    if (self.pos[slot] == len(e.req.prompt)
                            and id(e.req) not in self.step_logits):
                        self.step_logits[id(e.req)] = np.asarray(
                            logits[slot], np.float32)
                return logits, cache
            self._decode = recording_step

        def _emit_first_token(self, e, last_logits, prompt_len):
            import numpy as np
            self.prefill_logits[id(e.req)] = np.asarray(last_logits[0],
                                                        np.float32)
            super()._emit_first_token(e, last_logits, prompt_len)

    return RecordingEngine


def build_model(cfg, seed: int):
    import jax
    from repro.models import init_params
    t0 = time.perf_counter()
    # one compiled program: eager init compiles each op on its own
    params = jax.block_until_ready(jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed)))
    n = sum(x.size for x in jax.tree.leaves(params))
    info(f"built {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"vocab {cfg.vocab_size}, {n} params in {cfg.dtype} "
         f"({time.perf_counter() - t0:.1f} s)")
    return params


def quantize(cfg, params, calib):
    import jax
    from repro.core import quantize_model
    from repro.quant import QuantSpec, QuantizedTensor
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed",
                                 bits=BITS, group_size=GROUP_SIZE)
    t0 = time.perf_counter()
    qparams, report = quantize_model(cfg, params, calib, spec=spec)
    qparams = jax.block_until_ready(qparams)
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    qts = [x for x in jax.tree.leaves(qparams, is_leaf=is_qt) if is_qt(x)]
    check(qts and all(q.bits == BITS and q.group_size == GROUP_SIZE
                      for q in qts),
          f"expected every quantized leaf at w{BITS}/g{GROUP_SIZE}")
    info(f"quantized {len(report)} weight stacks ({len(qts)} packed "
         f"leaves) with {spec.method} w{BITS} group_size={GROUP_SIZE} on "
         f"{len(calib)} calibration batches of {tuple(calib[0].shape)}; "
         f"packed bytes {sum(q.packed_bytes() for q in qts)} "
         f"({time.perf_counter() - t0:.1f} s)")
    return qparams


def make_prompts(seed: int):
    import numpy as np
    from repro.data.pretrained import corpus_tokens
    toks = corpus_tokens("wiki", split="eval")
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, len(toks) - max(PROMPT_LENS), len(PROMPT_LENS))
    return [np.asarray(toks[s:s + n], np.int32)
            for s, n in zip(starts, PROMPT_LENS)]


def serve(cfg, params, prompts, *, mesh=None, kv_bits=0, label=""):
    from repro.serve import Request
    eng = make_engine_class()(cfg, params, batch_size=BATCH,
                              max_len=MAX_LEN, cache_kind="paged",
                              page_size=PAGE_SIZE, mesh=mesh,
                              kv_bits=kv_bits)
    reqs = [Request(prompt=p, max_new_tokens=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    n_out = sum(len(r.out) for r in reqs)
    check(all(len(r.out) == MAX_NEW for r in reqs),
          f"{label}: every request should get {MAX_NEW} tokens")
    check(len(eng.prefill_logits) == len(reqs)
          and len(eng.step_logits) == len(reqs),
          f"{label}: missing recorded logits")
    info(f"{label}: served {len(reqs)} requests, "
         f"{sum(len(p) for p in prompts)} prompt + {n_out} generated "
         f"tokens in {wall:.1f} s wall incl. compiles; "
         f"{eng.kv.bytes_per_page()} B/page")
    return eng, reqs


def check_decode_kernels(cfg, params, eng):
    """The compiled decode step the engine runs must call the Pallas
    kernels: the BCQ GEMMs and paged attention."""
    import jax.numpy as jnp
    from repro.serve import compile_cache
    B, T = eng.kv.block_tables.shape
    zeros = jnp.zeros((B,), jnp.int32)
    t0 = time.perf_counter()
    text = compile_cache.get("decode_paged", cfg, None).lower(
        params, eng.cache, jnp.zeros((B, 1), jnp.int32), zeros,
        jnp.zeros((B, T), jnp.int32), zeros, zeros).compile().as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "compiled decode step has no tpu_custom_call")
    info(f"compiled decode step: {n} tpu_custom_call sites "
         f"({time.perf_counter() - t0:.1f} s)")


def reference_logits(cfg, qparams, rows):
    """Plain float32 forward over the dequantized weights. rows: one
    (prompt, first served token) pair per sequence; position
    len(prompt) - 1 holds the prefill logits and len(prompt) those of
    the first decode step. Returns [(prefill, step)] per row."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import forward
    from repro.quant import QuantizedTensor
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    p32 = jax.tree.map(
        lambda x: x.dequant(jnp.float32) if is_qt(x)
        else x.astype(jnp.float32), qparams, is_leaf=is_qt)
    cfg32 = cfg.replace(dtype="float32")
    toks = np.zeros((len(rows), max(len(p) for p, _ in rows) + 1), np.int32)
    for i, (p, first) in enumerate(rows):
        toks[i, :len(p)] = p
        toks[i, len(p)] = first
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: forward(cfg32, p, t, remat="none")[0])(
            p32, jnp.asarray(toks))
    logits = np.asarray(logits, np.float32)
    info(f"float32 reference forward over {toks.shape} tokens "
         f"({time.perf_counter() - t0:.1f} s)")
    return [(logits[i, len(p) - 1], logits[i, len(p)])
            for i, (p, _) in enumerate(rows)]


def compare(label, eng, reqs, want, tol):
    """want: [(prefill logits, first decode logits)] per request."""
    worst = 0.0
    for i, (r, (w_pre, w_step)) in enumerate(zip(reqs, want)):
        e_pre = rel_rms(eng.prefill_logits[id(r)], w_pre)
        e_step = rel_rms(eng.step_logits[id(r)], w_step)
        worst = max(worst, e_pre, e_step)
        info(f"{label} request {i}: rel RMS logit error prefill "
             f"{e_pre:.5f}, first decode step {e_step:.5f}")
    check(worst <= tol, f"{label}: rel RMS logit error {worst:.5f} above "
                        f"the stated tolerance {tol}")
    print(f"{label}: logits within {tol} rel RMS of the reference "
          f"(worst {worst:.5f})", flush=True)


def first_token_rows(prompts, reqs):
    """(prompt, first served token) per request: what the reference
    forward needs to reproduce the prefill and first decode step."""
    return [(p, r.out[0]) for p, r in zip(prompts, reqs)]


def one_chip(cfg, qparams, prompts):
    """Serve with raw and 4-bit pages; compare both with the float32
    reference. Returns the raw-page engine."""
    eng, reqs = serve(cfg, qparams, prompts, label="raw KV pages")
    eng4, reqs4 = serve(cfg, qparams, prompts, kv_bits=KV_BITS,
                        label=f"{KV_BITS}-bit KV pages")
    # one reference pass over both runs: the 4-bit run may have picked
    # another first token, and its decode step is compared on that one
    want = reference_logits(cfg, qparams, first_token_rows(prompts, reqs)
                            + first_token_rows(prompts, reqs4))
    n = len(prompts)
    compare("raw KV pages vs float32 reference", eng, reqs, want[:n],
            TOL_SERVED)
    compare(f"{KV_BITS}-bit KV pages vs float32 reference", eng4, reqs4,
            want[n:], TOL_KV4)
    return eng


def mesh_vs_one_device(label, sharded, reqs, single, reqs1):
    """Gate a mesh run's logits on the one-device run's."""
    worst, n_step = 0.0, 0
    for i, (r1, r) in enumerate(zip(reqs1, reqs)):
        e_pre = rel_rms(sharded.prefill_logits[id(r)],
                        single.prefill_logits[id(r1)])
        worst = max(worst, e_pre)
        msg = f"request {i}: rel RMS logit error prefill {e_pre:.5f}"
        # the first decode step is comparable only when both runs fed
        # the same first token (a bf16 near-tie may flip the argmax)
        if r.out[0] == r1.out[0]:
            e_step = rel_rms(sharded.step_logits[id(r)],
                             single.step_logits[id(r1)])
            worst = max(worst, e_step)
            n_step += 1
            msg += f", first decode step {e_step:.5f}"
        else:
            msg += ", first tokens differ: decode step not compared"
        info(f"{label} vs one device {msg}")
    check(n_step > 0, f"{label}: no request fed the same first token as "
                      f"on one device")
    check(worst <= TOL_MESH, f"{label} vs one device: rel RMS logit error "
                             f"{worst:.5f} above the stated tolerance "
                             f"{TOL_MESH}")
    print(f"{label} vs one device: logits within {TOL_MESH} rel RMS "
          f"(worst {worst:.5f}, {len(reqs)} prefills, {n_step} decode "
          f"steps compared)", flush=True)


def on_mesh(cfg, qparams, prompts):
    """Serve on one device and over each (data, model) mesh; gate every
    run on the float32 reference and every mesh on the one device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_serve_mesh
    single, reqs1 = serve(cfg, qparams, prompts, label="one device")
    runs = []
    for data, model in MESHES:
        mesh = make_serve_mesh(data=data, model=model)
        label = f"mesh data={data} model={model}"
        placed = jax.device_put(qparams, NamedSharding(mesh, PartitionSpec()))
        runs.append((label, *serve(cfg, placed, prompts, mesh=mesh,
                                   label=label)))
    rows = first_token_rows(prompts, reqs1)
    for _, _, reqs in runs:
        rows += first_token_rows(prompts, reqs)
    want = reference_logits(cfg, qparams, rows)
    n = len(prompts)
    compare("one device vs float32 reference", single, reqs1, want[:n],
            TOL_SERVED)
    for i, (label, eng, reqs) in enumerate(runs, start=1):
        compare(f"{label} vs float32 reference", eng, reqs,
                want[i * n:(i + 1) * n], TOL_SERVED)
        mesh_vs_one_device(label, eng, reqs, single, reqs1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-serving phase over "
                         "the 4-device meshes, against one device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the prompts")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU visible; nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, {len(devices)} visible", file=sys.stderr)
        return 2

    from benchmarks.common import calib_batches_for
    from repro.configs import get_config
    from repro.serve.compile_cache import enable_persistent_cache
    info(f"persistent compile cache: {enable_persistent_cache()}")

    t_all = time.perf_counter()
    cfg = get_config(ARCH)
    params = build_model(cfg, args.seed)
    qparams = quantize(cfg, params,
                       calib_batches_for("wiki")[:CALIB_BATCHES])
    del params
    prompts = make_prompts(args.seed)
    if args.chips == 1:
        eng = one_chip(cfg, qparams, prompts)
        check_decode_kernels(cfg, qparams, eng)
    else:
        on_mesh(cfg, qparams, prompts)
    info(f"all phases passed in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
