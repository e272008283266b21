"""Production training launcher: mesh-aware pjit train loop with
checkpoint/auto-resume. On a real TPU slice this is launched once per
host (jax.distributed initializes from the TPU environment); in this
container it runs on the 1-device host mesh with the same code path.

  PYTHONPATH=src python -m repro.launch.train --arch tiny-lm --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.ckpt import CheckpointManager
from repro.data import batches, token_stream
from repro.dist.sharding import (inputs_shardings, opt_state_shardings,
                                 params_shardings)
from repro.dist.context import mesh_context
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import init_params
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", choices=["host", "pod", "multipod"],
                    default="host")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--ckpt-dir", default="artifacts/launch_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()

    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")

    cfg = get_config(args.arch).replace(dtype=args.dtype)
    opt_cfg = AdamWConfig(lr=1e-3, master_fp32=args.dtype == "bfloat16")
    toks = token_stream("wiki", 400_000)
    data = batches(toks, args.batch, args.seq, seed=0)

    with mesh_context(mesh):
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        opt_state = init_train_state(cfg, params, opt_cfg)
        p_sh = params_shardings(cfg, params, mesh)
        o_sh = opt_state_shardings(cfg, opt_state, mesh)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)

        ckpt = CheckpointManager(args.ckpt_dir)
        start = 0
        if ckpt.latest_step() is not None:
            state, meta = ckpt.restore({"params": params, "opt": opt_state})
            params = jax.device_put(state["params"], p_sh)
            opt_state = jax.device_put(state["opt"], o_sh)
            start = meta["step"]
            print(f"resumed from step {start}")

        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg, microbatches=args.microbatches,
                            grad_compress=args.grad_compress,
                            total_steps=args.steps),
            in_shardings=(p_sh, o_sh, None),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1))

        for step in range(start, args.steps):
            batch = next(data)
            t0 = time.time()
            params, opt_state, m = step_fn(params, opt_state, batch)
            loss = float(m["loss"])
            if (step + 1) % 10 == 0:
                print(f"step {step+1:5d} loss {loss:.4f} "
                      f"({time.time()-t0:.2f}s)", flush=True)
            if (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
        ckpt.save(args.steps, {"params": params, "opt": opt_state},
                  block=True)
    print("training complete")


if __name__ == "__main__":
    main()
