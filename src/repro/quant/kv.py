"""Binary-coded KV cache quantization: the storage format for quantized
page pools (models/attention.py:init_paged_kv(kv_bits=...)).

Each K/V vector of head_dim entries is stored as GPTQT's binary-coding
representation — the same alphas + sign-bitplane form the weight path
uses (core/binary_coding.py:bcq_greedy), fitted *per token, per KV head,
per contiguous head_dim group*:

    x[g*gs:(g+1)*gs] ~= beta_g + sum_i alpha_{g,i} * s_{g,i}

with s in {-1,+1} packed 32 signs per uint32 word along head_dim
(quant/packing.py:pack_signs_last). The coding is greedy residual sign
coding plus a mean offset (beta): per bit, alpha = mean|r| and
s = sign(r) — the closed-form per-step optimum the weight solvers start
from. Quantization happens on-write inside the jitted decode/extend/
scatter steps (it is a handful of vector ops per token), dequantization
happens inside the paged-attention kernel's VMEM accumulator loop
(kernels/paged_attention.py:paged_attention_quant) or the jnp oracle
(kernels/ref.py:paged_attention_quant_ref).

Layout per (token, head), head_dim = hd, G = hd / group_size:
    codes  (..., bits, hd/32)  uint32   sign bitplanes
    alphas (..., G, bits)      float32  per-group magnitudes
    betas  (..., G)            float32  per-group offsets

Page pools store these flattened to one row per token, heads
outermost (`kv_pool_rows`: codes (..., Hkv*bits*hd/32), alphas
(..., Hkv*G*bits), betas (..., Hkv*G)), so the TPU tiles pages
lane-dense; `kv_pool_views` restores the per-head shapes.

Bytes per (token, head): 4*bits*hd/32 + 4*G*bits + 4*G, vs 4*hd for an
fp32 page and 2*hd for bf16 — at hd=64, bits=4, G=1: 52 B vs 256/128 B
(4.9x / 2.5x). `kv_bytes_per_token_head` is the single owner of that
arithmetic (EngineStats and the capacity bench both read it).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.quant.packing import WORD, pack_signs_last, unpack_signs_last


def kv_layout(head_dim: int, kv_bits: int, kv_group_size: int = 0):
    """Validate a quantized-KV layout; returns (G, words_per_head).
    head_dim must be a multiple of 32 (signs pack with no padding) and
    kv_group_size (0 = one group spanning head_dim) must divide it."""
    if kv_bits < 1:
        raise ValueError(f"kv_bits must be >= 1, got {kv_bits}")
    if head_dim % WORD:
        raise ValueError(
            f"quantized KV needs head_dim % {WORD} == 0 (sign words pack "
            f"along head_dim), got head_dim={head_dim}")
    gs = kv_group_size or head_dim
    if head_dim % gs:
        raise ValueError(
            f"kv_group_size={gs} must divide head_dim={head_dim}")
    return head_dim // gs, head_dim // WORD


# alternating-refinement rounds inside kv_quantize: greedy residual
# coding alone saturates around 10% relative error regardless of bits
# (each bit only fixes the sign pattern the previous residual left);
# LS-refit + nearest-level-reassign rounds (Eq. 4, the same refinement
# core/binary_coding.py:bcq_alternating applies to weights) restore the
# expected per-bit decay. 6 rounds puts 4-bit coding at ~11% relative
# error — the level where greedy decode on the toy model is
# token-identical to the fp pool (tests/test_kv_quant.py) — at a cost
# of a few batched (bits x bits) solves per written token, noise next
# to the attention math itself. Read at trace time: a process that
# wants a different trade-off sets this before building engines.
KV_REFINE_ITERS = 6


def kv_quantize(x, kv_bits: int, kv_group_size: int = 0,
                iters: int | None = None):
    """Binary-code vectors along the last axis. x (..., hd) float ->
    (codes (..., bits, hd/32) u32, alphas (..., G, bits) f32,
    betas (..., G) f32). Greedy residual coding per contiguous group,
    then `iters` (default KV_REFINE_ITERS, resolved at trace time)
    alternating rounds: refit alphas by per-group least squares,
    reassign each entry to the nearest of the 2^bits representable
    levels."""
    if iters is None:
        iters = KV_REFINE_ITERS
    hd = x.shape[-1]
    G, _ = kv_layout(hd, kv_bits, kv_group_size)
    gs = hd // G
    xg = x.astype(jnp.float32).reshape(*x.shape[:-1], G, gs)
    beta = jnp.mean(xg, axis=-1)                         # (..., G)
    r0 = xg - beta[..., None]
    r = r0
    alphas, signs = [], []
    for _ in range(kv_bits):
        s = jnp.where(r >= 0, 1.0, -1.0)
        a = jnp.mean(jnp.abs(r), axis=-1)                # (..., G)
        alphas.append(a)
        signs.append(s)
        r = r - a[..., None] * s
    S = jnp.stack(signs, axis=-2)                        # (..., G, bits, gs)
    a = jnp.stack(alphas, axis=-1)                       # (..., G, bits)
    if iters:
        from repro.core.binary_coding import sign_combos
        combos = jnp.asarray(sign_combos(kv_bits))       # (L, bits)
        eye = jnp.eye(kv_bits, dtype=jnp.float32)
        for _ in range(iters):
            # refit: per-group LS  (S S^T) a = S r0
            Gm = jnp.einsum("...ik,...jk->...ij", S, S) + 1e-6 * eye
            rhs = jnp.einsum("...ik,...k->...i", S, r0)
            a = jnp.abs(jnp.linalg.solve(Gm, rhs[..., None])[..., 0])
            # reassign: nearest of the 2^bits levels
            levels = jnp.einsum("...b,lb->...l", a, combos)  # (..., G, L)
            idx = jnp.argmin(
                jnp.abs(r0[..., None, :] - levels[..., None]), axis=-2)
            S = jnp.moveaxis(combos[idx], -1, -2)        # (..., G, bits, gs)
    signs = jnp.moveaxis(S, -2, -3)                      # (..., bits, G, gs)
    signs = signs.reshape(*x.shape[:-1], kv_bits, hd)
    return pack_signs_last(signs), a, beta


def kv_dequantize(codes, alphas, betas, dtype=jnp.float32):
    """Inverse of kv_quantize: codes (..., bits, hd/32) u32, alphas
    (..., G, bits), betas (..., G) -> (..., hd) in `dtype`."""
    signs = unpack_signs_last(codes)                     # (..., bits, hd)
    *lead, bits, hd = signs.shape
    G = betas.shape[-1]
    sg = signs.reshape(*lead, bits, G, hd // G)
    w = jnp.einsum("...bgk,...gb->...gk", sg,
                   alphas.astype(jnp.float32)) + betas[..., None]
    return w.reshape(*lead, hd).astype(dtype)


def kv_pool_rows(codes, alphas, betas):
    """Flatten coded vectors to the page-pool layout: one lane-dense row
    per token, heads outermost. codes (..., Hkv, bits, hd/32) ->
    (..., Hkv*bits*hd/32); alphas (..., Hkv, G, bits) -> (..., Hkv*G*bits);
    betas (..., Hkv, G) -> (..., Hkv*G). A (bits, hd/32) minor tile
    would be padded to a whole (8, 128) TPU tile in HBM and VMEM."""
    return (codes.reshape(*codes.shape[:-3], -1),
            alphas.reshape(*alphas.shape[:-3], -1),
            betas.reshape(*betas.shape[:-2], -1))


def kv_pool_views(codes, alphas, betas, n_kv_heads: int):
    """Inverse of kv_pool_rows: per-head (codes, alphas, betas) views of
    page-pool rows. bits and G follow from the row widths."""
    G = betas.shape[-1] // n_kv_heads
    bits = alphas.shape[-1] // betas.shape[-1]
    return (codes.reshape(*codes.shape[:-1], n_kv_heads, bits, -1),
            alphas.reshape(*alphas.shape[:-1], n_kv_heads, G, bits),
            betas.reshape(*betas.shape[:-1], n_kv_heads, G))


def kv_bytes_per_token_head(head_dim: int, kv_bits: int,
                            kv_group_size: int = 0,
                            dtype_itemsize: int = 4) -> int:
    """Device bytes one (token, KV head) vector occupies. kv_bits=0 is
    the unquantized layout (head_dim raw entries of the pool dtype)."""
    if not kv_bits:
        return head_dim * dtype_itemsize
    G, hdw = kv_layout(head_dim, kv_bits, kv_group_size)
    # codes u32 + alphas f32 + betas f32
    return 4 * kv_bits * hdw + 4 * G * kv_bits + 4 * G
