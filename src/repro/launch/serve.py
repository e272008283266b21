"""Serving launcher: loads (or trains) a model, optionally GPTQT-quantizes
it, and serves a demo request batch through the continuous-batching
engine. Quantized models persist as packed artifacts (repro.ckpt.packed)
so a relaunch boots without re-running calibration or the GPTQ solves:

  # quantize once, save the packed artifact, serve
  PYTHONPATH=src python -m repro.launch.serve --quant 3 \\
      --save-quantized artifacts/packed/tiny-w3 --requests 6

  # every later launch: skip calibration/GPTQ entirely
  PYTHONPATH=src python -m repro.launch.serve \\
      --load-quantized artifacts/packed/tiny-w3 --requests 6

  # sharded serving: the packed artifact loads straight onto a 2-way
  # data mesh (per-leaf PartitionSpecs from the v3 manifest) and the
  # paged page pool is partitioned over the same axis. `--devices 2`
  # fakes two CPU devices for a rehearsal; on a TPU host leave it out
  PYTHONPATH=src python -m repro.launch.serve --devices 2 --mesh 2,1 \\
      --load-quantized artifacts/packed/tiny-w3 --requests 6

A process holds the chips it touches, so run one launcher per host.
"""
from __future__ import annotations

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU rehearsal only: force N host devices via "
                         "XLA_FLAGS as a stand-in for a multi-chip mesh "
                         "(must be set before jax initializes, so it is "
                         "a launcher flag); on a TPU host the chips are "
                         "the devices")
    ap.add_argument("--mesh", default=None, metavar="D,M",
                    help="serve over a (data, model) mesh, e.g. 2,1: "
                         "the paged KV pool shards its pages over the "
                         "data axis and --load-quantized places packed "
                         "leaves onto the mesh directly")
    ap.add_argument("--quant", type=int, default=0,
                    help="quantization bits (0 = dense)")
    ap.add_argument("--method", default=None,
                    help="registered quantizer name (default gptqt; see "
                         "docs/QUANT.md)")
    ap.add_argument("--group-size", type=int, default=0,
                    help="K entries per scale group (0 = per-channel); "
                         "must divide every quantized leaf's K_in")
    ap.add_argument("--suggest-overrides", action="store_true",
                    help="run the FineQuant-style sensitivity sweep and "
                         "print a paste-ready OverrideRule tuple instead "
                         "of serving")
    ap.add_argument("--bytes-budget", type=int, default=None,
                    metavar="BYTES",
                    help="with --suggest-overrides: spend this many extra "
                         "checkpoint bytes greedily by error reduction "
                         "per byte (default: bump the top-quantile "
                         "sensitive leaves regardless of size)")
    ap.add_argument("--save-quantized", default=None, metavar="DIR",
                    help="write the packed model artifact after quantizing")
    ap.add_argument("--load-quantized", default=None, metavar="DIR",
                    help="boot from a packed artifact (skips training, "
                         "calibration and quantization)")
    ap.add_argument("--train-steps", type=int, default=300,
                    help="tiny-LM pretraining steps (ignored with "
                         "--load-quantized)")
    ap.add_argument("--cache", default="auto",
                    choices=("auto", "dense", "paged"),
                    help="cache backend: auto picks paged when a mesh, "
                         "kv-bits, speculation, or a non-attention block "
                         "pattern (MLA latents, Mamba state slabs) asks "
                         "for it; paged forces the paged stack and "
                         "prints its capacity banner")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--kv-bits", type=int, default=0,
                    help="binary-code the KV page pool at this many bits "
                         "per coefficient (0 = raw fp pages); implies "
                         "the paged cache backend")
    ap.add_argument("--kv-group-size", type=int, default=0,
                    help="head_dim entries per KV scale group (0 = one "
                         "group per head vector); must divide head_dim")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-speculative decoding: a low-bit draft "
                         "view of the SAME packed weights proposes K "
                         "tokens per tick, one batched target pass "
                         "verifies them (greedy acceptance); implies "
                         "the paged cache backend; needs quantized "
                         "params (--quant or --load-quantized)")
    ap.add_argument("--draft-bits", type=int, default=2,
                    help="code planes the speculative draft keeps "
                         "(< the target's bits); draft scales come "
                         "from the artifact's v4 draft block when "
                         "present, else an on-the-fly LS re-fit")
    args = ap.parse_args()

    if args.devices:
        # must land before the first jax import anywhere below
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax

    from repro.configs import get_config
    from repro.data import ByteTokenizer
    from repro.serve import Request, ServeEngine
    from repro.serve.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        d, m = (int(x) for x in args.mesh.replace("x", ",").split(","))
        mesh = make_serve_mesh(data=d, model=m)
        print(f"serving over mesh data={d} model={m}")

    tok = ByteTokenizer()
    if args.suggest_overrides:
        from benchmarks.common import calib_batches_for
        from repro.data.pretrained import get_trained_lm
        from repro.quant import (QuantSpec, format_overrides, format_report,
                                 sensitivity_sweep, suggest_overrides)

        cfg, params = get_trained_lm(args.arch, steps=args.train_steps)
        spec = QuantSpec.from_config(
            cfg.quant, method=args.method or "gptqt",
            bits=args.quant or cfg.quant.bits,
            group_size=args.group_size)
        scores = sensitivity_sweep(cfg, params, calib_batches_for("wiki"),
                                   spec=spec)
        print(format_report(scores))
        rules = suggest_overrides(scores, base_bits=spec.bits,
                                  bytes_budget=args.bytes_budget)
        if args.bytes_budget is not None:
            from repro.quant.search import bump_cost_bytes
            spent = sum(bump_cost_bytes(s, spec.bits, spec.bits + 1)
                        for s in scores
                        if any(r.pattern == s.path for r in rules))
            print(f"\n# bytes budget {args.bytes_budget}: bumped "
                  f"{len(rules)}/{len(scores)} leaves from w{spec.bits} "
                  f"to w{spec.bits + 1} ({spent} bytes spent); paste "
                  f"into QuantSpec(..., overrides=...):")
        else:
            print(f"\n# most sensitive {len(rules)}/{len(scores)} leaves "
                  f"bumped from w{spec.bits} to w{spec.bits + 1}; paste "
                  f"into QuantSpec(..., overrides=...):")
        print(format_overrides(rules))
        return

    if args.load_quantized:
        if (args.quant or args.save_quantized or args.group_size
                or args.method):
            ap.error("--load-quantized boots the artifact as-is; it is "
                     "incompatible with --quant/--save-quantized/"
                     "--group-size/--method (re-quantize and re-save to "
                     "change them)")
        from repro.ckpt.packed import load_packed
        params, spec, meta = load_packed(args.load_quantized, mesh=mesh)
        arch = meta.get("arch", args.arch)
        # mirror get_trained_lm's config construction; all weights come
        # from the artifact, so no training or calibration happens here
        cfg = get_config(arch).replace(dtype="float32", remat="none")
        desc = (f"{spec.method} w{spec.bits}" if spec is not None
                else "unknown spec")
        print(f"loaded packed model '{arch}' ({desc}) from "
              f"{args.load_quantized} — calibration/GPTQ skipped")
    else:
        from benchmarks.common import calib_batches_for
        from repro.core import quantize_model
        from repro.data.pretrained import get_trained_lm
        from repro.quant import QuantSpec

        cfg, params = get_trained_lm(args.arch, steps=args.train_steps)
        if args.quant:
            spec = QuantSpec.from_config(
                cfg.quant, method=args.method or "gptqt", mode="packed",
                bits=args.quant, group_size=args.group_size)
            gdesc = (f", group_size={spec.group_size}" if spec.group_size
                     else "")
            print(f"quantizing with {spec.method} to {spec.bits} bits "
                  f"(packed{gdesc}) ...")
            params, _ = quantize_model(cfg, params,
                                       calib_batches_for("wiki"), spec=spec)
            if args.save_quantized:
                from repro.ckpt.packed import save_packed
                # store the draft block whenever a draft is expressible:
                # the re-fit scales are tiny and let any later
                # `--speculate` boot skip the on-the-fly refit
                d_bits = (args.draft_bits
                          if 0 < args.draft_bits < args.quant else None)
                out = save_packed(args.save_quantized, params, spec=spec,
                                  meta={"arch": args.arch},
                                  draft_bits=d_bits)
                print(f"saved packed artifact to {out}"
                      + (f" (w{d_bits} draft scales included)"
                         if d_bits else ""))
        elif args.save_quantized:
            ap.error("--save-quantized requires --quant")

    batch = args.batch_size
    if mesh is not None:
        # every page-pool shard serves an equal slice of the batch
        from repro.dist.sharding import mesh_axis_sizes
        d = int(mesh_axis_sizes(mesh).get("data", 1))
        if batch % d:
            batch = -(-batch // d) * d
            print(f"batch_size rounded {args.batch_size} -> {batch} "
                  f"(must split over {d} data shards)")
    draft_params = None
    if args.speculate:
        from repro.quant.draft import make_draft_params
        scales_tree = None
        if args.load_quantized:
            from repro.ckpt.packed import load_draft_scales
            scales_tree = load_draft_scales(args.load_quantized)
            print("draft scales: "
                  + ("manifest v4 draft block" if scales_tree is not None
                     else "on-the-fly LS re-fit (no v4 draft block)"))
        draft_params = make_draft_params(params, args.draft_bits,
                                         scales_tree)
    paged = mesh is not None or args.kv_bits > 0 or args.speculate > 0
    if args.cache == "paged":
        paged = True
    elif args.cache == "dense":
        if paged:
            ap.error("--cache dense conflicts with --mesh/--kv-bits/"
                     "--speculate (each requires the paged backend)")
    eng = ServeEngine(cfg, params, batch_size=batch, max_len=160,
                      cache_kind="paged" if paged else "dense",
                      mesh=mesh, kv_bits=args.kv_bits,
                      kv_group_size=args.kv_group_size,
                      speculate=args.speculate,
                      draft_bits=args.draft_bits,
                      draft_params=draft_params)
    if paged:
        kv = eng.kv
        kind = "latent" if cfg.mla is not None else "kv"
        print(f"paged {kind} cache: {kv.n_pages} pages x "
              f"{kv.page_size} tok, {kv.bytes_per_page()} B/page")
        if eng.slab is not None:
            sl = eng.slab
            print(f"state slab pool: {sl.usable_slabs} usable slabs "
                  f"({sl.n_shards} reserve), {sl.bytes_per_slab()} B/slab")
    if args.kv_bits:
        kv = eng.kv
        raw = kv.__class__(cfg, n_pages=kv.n_pages,
                           page_size=kv.page_size, max_seqs=kv.max_seqs,
                           dtype=cfg.dtype,
                           create_pool=False).bytes_per_page()
        print(f"quantized KV cache: {args.kv_bits}-bit binary-coded "
              f"pages, {kv.bytes_per_page()} B/page vs {raw} B/page raw "
              f"({raw / kv.bytes_per_page():.1f}x capacity)")
    if mesh is not None:
        kv = eng.kv
        print(f"sharded page pool: {kv.n_shards} shards x "
              f"{kv.pages_per_shard} pages "
              f"({kv.usable_in_shard(0)} usable + 1 reserve each, "
              f"page_size={kv.page_size})")
    seeds = ["the ancient city", "a famous museum", "this railway",
             "the council", "another region", "the early dynasty"]
    reqs = [Request(prompt=tok.encode(seeds[i % len(seeds)]),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    eng.run(reqs)
    tput = eng.stats["tokens"] / max(eng.stats["decode_s"], 1e-9)
    dev = jax.devices()[0]
    print(f"served {len(reqs)} requests, {eng.stats['tokens']} tokens, "
          f"decode throughput {tput:.1f} tok/s on {dev.platform} "
          f"({dev.device_kind})")
    st = eng.stats
    ticks = st["ticks"]
    if ticks:
        split = " / ".join(
            f"{1e3 * st.get(f'serve.decode.{k}_s', 0.0) / ticks:.2f}"
            for k in ("grow", "step", "readback", "emit"))
        print(f"per decode tick (ms, {ticks} ticks): grow / step / "
              f"readback / emit {split}; {st['decode_rows'] / ticks:.1f} "
              f"of {eng.B} rows live, "
              f"{st.get('bt_rows_synced', 0) / ticks:.2f} block-table "
              f"rows pushed")
    if st.get("admit_prompt_tokens"):
        print(f"prefix cache: {st['admit_prefix_tokens']} of "
              f"{st['admit_prompt_tokens']} admitted prompt tokens "
              f"attached from the index")
    for r in reqs[:3]:
        print(" ", repr(tok.decode(r.out)))


if __name__ == "__main__":
    main()
