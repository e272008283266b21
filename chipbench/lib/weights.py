"""Seeded random weights, drawn alike for the program and the reference.

`layer_weights` and `embedding` draw a model's bf16 weights from the
seed, one layer at a time. Both sides use them: the program's served
parameters (chipbench/programs/<program>.py) and the plain reference
(configs/<reference>.py), which codes and runs them on its own.

The served form is what GPTQT emits (quant/qlinear.py:QuantizedTensor:
w`bits` sign planes packed 32 per uint32 along K, float32 alphas
(G, N, bits) and betas (G, N) per group of `group_size` rows). `_code`
makes it with the repo's calibration-free greedy residual coding
(core/binary_coding.py:bcq_greedy), inside the program's jitted maker;
GPTQT's calibrated solve is offline work and is not run here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# per-layer matrices, (K, N) with y = x @ W; norms are stored as their
# deviation from 1 (scale = 1 + delta), the program's convention
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("wg", "wu", "wd")
NORM_STD = 0.1
EMBED_STD = 0.02


def dims(config: dict) -> dict:
    """The sizes a run needs, from a configuration file's keys. A file
    without `head_dim` has hidden_size / heads; one without experts has
    0 experts and 0 of them per token (`f` is then the dense MLP's
    width, else each expert's)."""
    c = config
    return {"d": c["hidden_size"], "f": c["intermediate_size"],
            "layers": c["num_hidden_layers"],
            "heads": c["num_attention_heads"],
            "kv_heads": c["num_key_value_heads"],
            "hd": c.get("head_dim")
            or c["hidden_size"] // c["num_attention_heads"],
            "vocab": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": float(c["rope_theta"]),
            "experts": c.get("num_local_experts", 0),
            "top_k": c.get("num_experts_per_tok", 0)}


def root_key(seed: int):
    """A key from any whole number, also one above 32 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def shapes(dm: dict) -> dict:
    """One layer's weights. With experts the MLP's matrices are stacks
    (experts, K, N) and a router (d, experts) joins them."""
    d, f, hd = dm["d"], dm["f"], dm["hd"]
    qd, kd = dm["heads"] * hd, dm["kv_heads"] * hd
    mlp = {"wg": (d, f), "wu": (d, f), "wd": (f, d)}
    if dm.get("experts"):
        E = dm["experts"]
        mlp = {n: (E, *s) for n, s in mlp.items()} | {"router": (d, E)}
    return {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd), "wo": (qd, d),
            **mlp, "ln": (d,), "ln2": (d,), "qn": (hd,), "kn": (hd,)}


def layer_weights(key, i, dm: dict) -> dict:
    """Layer i's bf16 weights: matrices N(0, 1/K), norm deltas
    N(0, NORM_STD). `i` may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    out = {}
    for j, (name, shp) in enumerate(sorted(shapes(dm).items())):
        kj = jax.random.fold_in(k, j)
        std = NORM_STD if len(shp) == 1 else shp[-2] ** -0.5
        out[name] = (jax.random.normal(kj, shp, jnp.float32)
                     * std).astype(jnp.bfloat16)
    return out


def embedding(key, dm: dict):
    """(vocab, d) tied embedding and the final norm delta, bf16."""
    ke = jax.random.fold_in(key, 2)
    e = (jax.random.normal(ke, (dm["vocab"], dm["d"]), jnp.float32)
         * EMBED_STD).astype(jnp.bfloat16)
    kf = jax.random.fold_in(key, 3)
    fl = (jax.random.normal(kf, (dm["d"],), jnp.float32)
          * NORM_STD).astype(jnp.bfloat16)
    return e, fl


def _code(w, bits: int, group: int):
    """One (K, N) bf16 matrix -> QuantizedTensor, by the program's
    greedy residual coding over groups of `group` rows of K."""
    from repro.core.binary_coding import bcq_greedy
    from repro.core.rtn import group_rows
    from repro.quant.packing import pack_signs
    from repro.quant.qlinear import QuantizedTensor
    K, N = w.shape
    wg, G = group_rows(w.T.astype(jnp.float32), group)    # (N*G, group)
    alphas, signs = bcq_greedy(wg, bits)       # (N*G, b), (b, N*G, group)
    signs = signs.reshape(bits, N, K).transpose(0, 2, 1)   # (b, K, N)
    return QuantizedTensor(
        codes=pack_signs(signs > 0),
        alphas=alphas.reshape(N, G, bits).transpose(1, 0, 2),
        betas=jnp.zeros((G, N), jnp.float32), k_in=K,
        orig_dtype="bfloat16")

