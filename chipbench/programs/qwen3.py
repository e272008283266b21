"""The program side of the Qwen3 configurations: the repo's model config
for a configuration file, and the served parameter tree made from the
seed on the device.

    model_config(config) -> ModelConfig, checked against the file
    params(seed, config, mesh) -> w3 parameters, placed on `mesh`
                                  (one device when it is None)

The tree is a dense qk-norm GQA model with a tied embedding, every
projection coded into GPTQT's packed form (lib/weights.py:_code).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.lib.weights import (ATTN, MLP, _code, dims, embedding,
                                   layer_weights, root_key)


def model_config(config: dict):
    """The program's model config for a configuration file, checked key
    by key against the file so the file is what runs. A test at a small
    size hands its own config in as `config["_cfg"]`."""
    from repro.configs import get_config
    cfg = config["_cfg"] if "_cfg" in config else get_config(config["arch"])
    dm = dims(config)
    moe = cfg.moe
    got = {"d": cfg.d_model, "f": cfg.d_ff, "layers": cfg.n_layers,
           "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
           "hd": cfg.resolved_head_dim, "vocab": cfg.vocab_size,
           "eps": cfg.norm_eps, "theta": float(cfg.rope_theta),
           "experts": moe.n_experts if moe else 0,
           "top_k": moe.top_k if moe else 0}
    if got != dm or moe or not cfg.qk_norm or not cfg.tie_embeddings:
        raise ValueError(f"program config {cfg.name} {got} differs from "
                         f"the configuration file {dm}")
    return cfg.replace(dtype=config["serve"]["dtype"])


def params(seed: int, config: dict, mesh=None):
    """The served parameter tree, made on the device in one jitted call.
    The layers are drawn and coded one at a time (lax.map), so the bf16
    model never exists whole; on a mesh every leaf is made in its
    serving sharding (dist/sharding.py:params_shardings), so the coded
    model never exists whole on one chip either."""
    dm = dims(config)
    bits, group = config["quant"]["bits"], config["quant"]["group_size"]

    def build(key):
        def one(i):
            w = layer_weights(key, i, dm)
            return {"ln": w["ln"], "ln2": w["ln2"],
                    "attn": {**{n: _code(w[n], bits, group) for n in ATTN},
                             "qn": w["qn"], "kn": w["kn"]},
                    "mlp": {n: _code(w[n], bits, group) for n in MLP}}
        blocks = jax.lax.map(one, jnp.arange(dm["layers"]))
        e, fl = embedding(key, dm)
        return {"embed": e, "final_ln": fl, "blocks": {"L0": blocks}}

    key = root_key(seed)
    if mesh is None:
        return jax.jit(build)(key)
    from repro.dist.sharding import params_shardings
    shapes = jax.eval_shape(build, jax.ShapeDtypeStruct(key.shape,
                                                        key.dtype))
    out = params_shardings(model_config(config), shapes, mesh)
    return jax.jit(build, out_shardings=out)(key)
