"""Plain float32 reference of a dense Qwen3 decoder (qk-norm GQA,
SwiGLU, tied embedding), as published for Qwen/Qwen3-0.6B and -4B.

It imports nothing of the program. It draws the same seeded bf16
weights as the served model (lib/weights.py), codes each matrix itself
by greedy residual binary coding over groups of K rows, and runs the
forward pass in float32 at `highest` matmul precision, one layer at a
time and in blocks of rows, so that a 4B model fits beside nothing
else. Only the per-position numbers the check needs leave the device:
the best logit, the logit of the served token and, for the control,
the reference's logit of the token the lower precision ranks first.

The control (`control=True`, or "f8") runs the same forward a second
time with every matmul operand and the residual stream between
sublayers rounded to float8 e4m3 under one scale per tensor: the
reference in the nearest precision below the bf16 that the
configuration serves in, held where the program holds bf16 (the
residual stream, q, k, v and the weights it multiplies by).
`control="bf16"` rounds the same tensors to bf16 instead: a witness at
the program's own precision, for readings only (chipbench/calibrate.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib.weights import (ATTN, MLP, dims, embedding,
                                   layer_weights, root_key)

ROWS_PER_BLOCK = 8      # sequences per reference call
LEN_UNIT = 512          # padded lengths are multiples of this
Q_CHUNK = 256           # query rows per attention block
HEAD_CHUNK = 128        # positions per head block
F8_MAX = 448.0          # largest float8 e4m3 value


def f8(a):
    """Round to float8 e4m3 with one scale per tensor, back to f32."""
    s = jnp.max(jnp.abs(a)) / F8_MAX + 1e-30
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _same(a):
    return a


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


# rounding of matmul operands on the second pass, by `control`
LOWER = {True: f8, "f8": f8, "bf16": bf16}


def code(w, bits: int, group: int):
    """(K, N) weight -> float32 sum_i alpha_i * sign_i, each group of
    `group` rows of K coded on its own, greedily: sign of the residual
    (>= 0 is +1), alpha its mean magnitude."""
    K, N = w.shape
    r = w.astype(jnp.float32).T.reshape(N, K // group, group)
    acc = jnp.zeros_like(r)
    for _ in range(bits):
        b = jnp.where(r >= 0, 1.0, -1.0)
        a = jnp.mean(jnp.abs(r), axis=-1, keepdims=True)
        acc = acc + a * b
        r = r - a * b
    return acc.reshape(N, K).T


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotate halves; x (B, S, H, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, lo):
    """Causal GQA; q (B, S, H, hd), k/v (B, S, Hkv, hd)."""
    B, S, H, hd = q.shape
    hkv = k.shape[2]
    qg = q.reshape(B, S // Q_CHUNK, Q_CHUNK, hkv, H // hkv, hd)
    kj = jnp.arange(S)

    def chunk(c):
        qc = qg[:, c]
        s = jnp.einsum("bqhrd,bkhd->bhrqk", lo(qc), lo(k)) * hd ** -0.5
        qi = c * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kj[None, :] <= qi[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhrqk,bkhd->bqhrd", lo(p), lo(v))

    out = jax.lax.map(chunk, jnp.arange(S // Q_CHUNK))  # (C,B,Q,hkv,r,hd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)


def block(x, w, g, dm, lo):
    B, S, _ = x.shape
    H, hkv, hd, eps = dm["heads"], dm["kv_heads"], dm["hd"], dm["eps"]
    mm = lambda a, b: jnp.matmul(lo(a), lo(b))
    x = lo(x)
    h = rms(x, g["ln"], eps)
    q = rms(mm(h, w["wq"]).reshape(B, S, H, hd), g["qn"], eps)
    k = rms(mm(h, w["wk"]).reshape(B, S, hkv, hd), g["kn"], eps)
    v = mm(h, w["wv"]).reshape(B, S, hkv, hd)
    q, k = rope(q, dm["theta"]), rope(k, dm["theta"])
    x = lo(x + mm(attention(q, k, v, lo).reshape(B, S, H * hd), w["wo"]))
    h = rms(x, g["ln2"], eps)
    return lo(x + mm(jax.nn.silu(mm(h, w["wg"])) * mm(h, w["wu"]),
                     w["wd"]))


@functools.partial(jax.jit, static_argnames=("dmt",))
def _embed(key, tokens, dmt):
    e, _ = embedding(key, dict(dmt))
    return e.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("dmt", "bits", "group",
                                             "control"))
def _layer(key, i, x, xc, dmt, bits, group, control):
    dm = dict(dmt)
    raw = layer_weights(key, i, dm)
    w = {n: code(raw[n], bits, group) for n in ATTN + MLP}
    g = {n: 1.0 + raw[n].astype(jnp.float32)
         for n in ("ln", "ln2", "qn", "kn")}
    x = block(x, w, g, dm, _same)
    if control:
        xc = block(xc, w, g, dm, LOWER[control])
    return x, xc


@functools.partial(jax.jit, static_argnames=("dmt", "control"))
def _head(key, x, xc, nxt, dmt, control):
    """Per position: best logit, logit of token `nxt`, the reference's
    logit of the control's first-ranked token, and the second-best
    logit."""
    dm = dict(dmt)
    e, fl = embedding(key, dm)
    e = e.astype(jnp.float32)
    scale = 1.0 + fl.astype(jnp.float32)
    B, S, d = x.shape
    n = S // HEAD_CHUNK

    def split(a):
        return a.reshape(B, n, HEAD_CHUNK, *a.shape[2:]).swapaxes(0, 1)

    def chunk(args):
        xs, xcs, ns = args
        lg = rms(xs, scale, dm["eps"]) @ e.T
        top2 = jax.lax.top_k(lg, 2)[0]
        best, second = top2[..., 0], top2[..., 1]
        served = jnp.take_along_axis(lg, ns[..., None], -1)[..., 0]
        if control:
            lo = LOWER[control]
            lc = lo(rms(xcs, scale, dm["eps"])) @ lo(e).T
            top = jnp.argmax(lc, -1)
            ctrl = jnp.take_along_axis(lg, top[..., None], -1)[..., 0]
        else:
            ctrl = best
        return best, served, ctrl, second

    outs = jax.lax.map(chunk, (split(x), split(xc), split(nxt)))
    return tuple(o.swapaxes(0, 1).reshape(B, S) for o in outs)


def score(config: dict, seed: int, rows, *, control: bool | str = False):
    """rows: [(seq, start)] with seq the prompt followed by the served
    tokens and start = len(prompt) - 1. For each row, arrays over the
    positions p = start .. len(seq) - 2, each predicting seq[p + 1]:
    {"best", "served", "ctrl", "second"} (ctrl equals best without
    control)."""
    dm = dims(config)
    dmt = tuple(sorted(dm.items()))
    q = config["quant"]
    key = root_key(seed)
    order = sorted(range(len(rows)), key=lambda r: len(rows[r][0]))
    out = [None] * len(rows)
    with jax.default_matmul_precision("highest"):
        for b0 in range(0, len(order), ROWS_PER_BLOCK):
            idx = order[b0:b0 + ROWS_PER_BLOCK]
            n_in = max(len(rows[r][0]) - 1 for r in idx)
            S = -(-n_in // LEN_UNIT) * LEN_UNIT
            toks = np.zeros((ROWS_PER_BLOCK, S), np.int32)
            nxt = np.zeros((ROWS_PER_BLOCK, S), np.int32)
            for j, r in enumerate(idx):
                seq = np.asarray(rows[r][0], np.int32)
                toks[j, :len(seq) - 1] = seq[:-1]
                nxt[j, :len(seq) - 1] = seq[1:]
            x = _embed(key, jnp.asarray(toks), dmt)
            xc = x
            for i in range(dm["layers"]):
                x, xc = _layer(key, i, x, xc, dmt, q["bits"],
                               q["group_size"], control)
            best, served, ctrl, second = (np.asarray(a) for a in _head(
                key, x, xc, jnp.asarray(nxt), dmt, control))
            for j, r in enumerate(idx):
                seq, start = rows[r]
                sl = slice(start, len(seq) - 1)
                out[r] = {"best": best[j, sl], "served": served[j, sl],
                          "ctrl": ctrl[j, sl], "second": second[j, sl]}
    return out
