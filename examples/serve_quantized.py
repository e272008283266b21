"""End-to-end driver: quantize a trained LM to packed 3-bit GPTQT binary
coding and serve batched requests through the continuous-batching engine
(the paper's deployment mode — weight-only quantized decode).

  PYTHONPATH=src:. python examples/serve_quantized.py

Multi-device quickstart (`--sharded`, a CPU rehearsal tool): the same
flow over a 2-way data mesh faked on CPU — quantize, save the packed
artifact, load it back *directly onto the mesh* (the v3 manifest
carries per-leaf PartitionSpecs), and serve with the paged KV pool
partitioned into one page-pool shard per data-axis device. Greedy outputs are checked
token-for-token against the single-device engine.

  PYTHONPATH=src:. python examples/serve_quantized.py --sharded
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

if "--sharded" in sys.argv:
    # must precede the first jax import: fake two host devices
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2"
                               ).strip()


def main():
    import jax
    from benchmarks.common import calib_batches_for
    from repro.core import quantize_model
    from repro.data import ByteTokenizer
    from repro.data.pretrained import get_trained_lm
    from repro.quant import QuantSpec, QuantizedTensor
    from repro.serve import Request, ServeEngine

    cfg, params = get_trained_lm("tiny-lm")
    tok = ByteTokenizer()

    print("quantizing to packed 3-bit GPTQT binary coding ...")
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qparams, _ = quantize_model(cfg, params, calib_batches_for("wiki"),
                                spec=spec)

    def tree_bytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    print(f"dense params:  {tree_bytes(params)/1e6:8.2f} MB (fp32)")
    print(f"packed params: {tree_bytes(qparams)/1e6:8.2f} MB "
          f"(GPTQT w3 binary coding)")

    prompts = [
        "the ancient city", "a famous museum", "this railway connected",
        "the council governed", "another region", "the early dynasty",
    ]
    reqs = [Request(prompt=tok.encode(p), max_new_tokens=24)
            for p in prompts]

    for label, ps, kw in (("dense", params, {}),
                          ("gptqt-w3", qparams, {}),
                          ("gptqt-w3+paged", qparams,
                           dict(cache_kind="paged", page_size=32))):
        eng = ServeEngine(cfg, ps, batch_size=3, max_len=128,
                          dtype="float32", **kw)
        t0 = time.time()
        done = eng.run([Request(prompt=r.prompt.copy(),
                                max_new_tokens=r.max_new_tokens)
                        for r in reqs])
        dt = time.time() - t0
        tput = eng.stats["tokens"] / max(eng.stats["decode_s"], 1e-9)
        print(f"\n[{label}] {eng.stats['tokens']} tokens in {dt:.2f}s "
              f"(decode throughput {tput:.1f} tok/s on CPU, "
              f"ttft {eng.stats['ttft_avg_s']:.3f}s)")
        for r, p in list(zip(done, prompts))[:3]:
            print(f"  '{p}' -> '{tok.decode(r.out)}'")

    if "--sharded" in sys.argv:
        sharded_quickstart(cfg, qparams, reqs, tok, prompts)


def sharded_quickstart(cfg, qparams, reqs, tok, prompts):
    """Serve the packed model over a 2-way data mesh: save the packed
    artifact, load it straight onto the mesh, shard the paged pool, and
    check greedy outputs against the single-device paged engine."""
    import tempfile

    import jax
    from repro.ckpt.packed import save_packed, load_packed
    from repro.launch.mesh import make_serve_mesh
    from repro.quant import QuantSpec
    from repro.serve import Request, ServeEngine

    assert len(jax.devices()) >= 2, "run with --sharded from the start"
    mesh = make_serve_mesh(data=2, model=1)
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    art = tempfile.mkdtemp() + "/packed-w3"
    save_packed(art, qparams, spec=spec, meta={"arch": cfg.name})
    # per-leaf placement from the manifest's PartitionSpecs: no
    # host-side full-tree materialization, no re-quantization
    mparams, _, _ = load_packed(art, mesh=mesh)

    def run(params, mesh=None):
        # batch_size splits evenly over the data shards (2 here)
        eng = ServeEngine(cfg, params, batch_size=4, max_len=128,
                          dtype="float32", cache_kind="paged",
                          page_size=32, mesh=mesh)
        done = eng.run([Request(prompt=r.prompt.copy(),
                                max_new_tokens=r.max_new_tokens)
                        for r in reqs])
        return [r.out for r in done], eng

    want, _ = run(qparams)
    got, eng = run(mparams, mesh)
    kv = eng.kv
    print(f"\n[sharded 2x1] page pool: {kv.n_shards} shards x "
          f"{kv.pages_per_shard} pages each "
          f"({kv.usable_in_shard(0) * kv.page_size} tokens/shard); "
          f"outputs match single-device: {got == want}")
    for out, p in list(zip(got, prompts))[:2]:
        print(f"  '{p}' -> '{tok.decode(out)}'")
    assert got == want, "sharded decode must be token-identical"


if __name__ == "__main__":
    main()
