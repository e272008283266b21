"""Pure-jnp oracles for the Pallas kernels.

`bcq_matmul_ref` is the correctness reference (dequantize, then matmul).
`bcq_matmul_bitplane_ref` is the GPU-LUT-GEMM-style reassociation
    y = sum_i alpha_i * (x @ S_i) + (sum_k x) * beta
— mathematically identical, but it costs `bits` MXU passes instead of
one; we keep it to *demonstrate* why the TPU adaptation fuses dequant
into a single GEMM instead (see DESIGN.md §2 and benchmarks/table4).

`paged_attention_ref` is the oracle for kernels/paged_attention.py and
also the non-TPU execution path for paged decode: it gathers each
sequence's pages through the block table and runs the same masked
softmax the dense `attn_decode` uses, so CPU tests can compare paged vs
dense decode token-for-token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant.packing import unpack_signs

NEG_INF = -1e30


def dequant_ref(codes, alphas, betas, k_in: int, dtype=jnp.float32):
    """codes (bits, K/32, N) u32; alphas (G, N, bits); betas (G, N)
    -> W (k_in, N). Group g's scales cover K rows [g*ceil(k_in/G),
    ...): exact contiguous groups when G divides k_in (the
    QuantizedTensor invariant), ragged-tail semantics otherwise."""
    signs = unpack_signs(codes, k_in)                    # (bits, K, N)
    G = alphas.shape[0]
    glen = -(-k_in // G)
    # scales may be bf16 in memory (packed artifacts); expand in fp32
    a = jnp.repeat(alphas.astype(jnp.float32),
                   glen, axis=0)[:k_in]                  # (K, N, bits)
    b = jnp.repeat(betas.astype(jnp.float32),
                   glen, axis=0)[:k_in]                  # (K, N)
    w = jnp.einsum("ikn,kni->kn", signs, a) + b
    return w.astype(dtype)


def bcq_matmul_ref(x, codes, alphas, betas, k_in: int):
    """x (..., k_in) -> (..., N)."""
    w = dequant_ref(codes, alphas, betas, k_in, dtype=jnp.float32)
    return jnp.einsum("...k,kn->...n", x.astype(jnp.float32), w).astype(x.dtype)


def bcq_gemv_ref(x, codes, alphas, betas, k_in: int):
    """Oracle for the decode-shaped kernel entry: same math as the GEMM
    (the gemv only retiles), so the reference is shared."""
    return bcq_matmul_ref(x, codes, alphas, betas, k_in)


def bcq_expert_matmul_ref(x, codes, alphas, betas, k_in: int):
    """Oracle for the batched-expert kernel: x (E, M, k_in); codes
    (E, bits, K/32, N); alphas (E, G, N, bits); betas (E, G, N)
    -> (E, M, N). Dequantize every expert (vmapped single-expert
    oracle), then one batched matmul."""
    w = jax.vmap(
        lambda c, a, b: dequant_ref(c, a, b, k_in, dtype=jnp.float32))(
        codes, alphas, betas)                            # (E, k_in, N)
    return jnp.einsum("emk,ekn->emn", x.astype(jnp.float32),
                      w).astype(x.dtype)


def _paged_attend(q, k, v, ctx_lens, *, window, cap):
    """Decode-time masked softmax over already-gathered K/V:
    q (B, Hkv, rep, hd); k/v (B, Hkv, K, hd); ctx_lens (B,)."""
    hd = q.shape[-1]
    logits = jnp.einsum("bhrd,bhkd->bhrk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    j = jnp.arange(k.shape[2])[None, :]
    ok = j < ctx_lens[:, None]
    if window is not None:
        ok &= (ctx_lens[:, None] - 1 - j) < window
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    w = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.einsum("bhrk,bhkd->bhrd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens, layer,
                        *, window=None, cap=None):
    """q (B, Hkv, rep, hd); k_pages/v_pages the stacked pools
    (L, P, page, Hkv, hd), of which layer `layer` (int32 scalar) is
    read; block_tables (B, T); ctx_lens (B,). Returns (B, Hkv, rep,
    hd)."""
    B, Hkv, rep, hd = q.shape
    page = k_pages.shape[2]
    T = block_tables.shape[1]
    # gather: (B, T, page, Hkv, hd) -> (B, Hkv, T*page, hd)
    k = k_pages[layer, block_tables].reshape(B, T * page, Hkv, hd)
    v = v_pages[layer, block_tables].reshape(B, T * page, Hkv, hd)
    return _paged_attend(q, k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), ctx_lens,
                         window=window, cap=cap)


def paged_attention_quant_ref(q, k_codes, k_alphas, k_betas, v_codes,
                              v_alphas, v_betas, block_tables, ctx_lens,
                              layer, *, window=None, cap=None):
    """Oracle for the fused-dequant kernel, and the non-TPU execution
    path for quantized paged decode: gather each sequence's binary-coded
    pages of layer `layer` through the block table, expand codes -> fp32
    K/V (quant/kv.py pool rows, stacked: codes (L, P, page,
    Hkv*bits*hd/32) u32, alphas (L, P, page, Hkv*G*bits), betas
    (L, P, page, Hkv*G)), then the same masked softmax as
    paged_attention_ref."""
    from repro.quant.kv import kv_dequantize, kv_pool_views

    B, Hkv, rep, hd = q.shape
    page = k_codes.shape[2]
    T = block_tables.shape[1]

    def pages(codes, alphas, betas):          # -> (B, T, page, Hkv, hd)
        return kv_dequantize(*kv_pool_views(
            codes[layer, block_tables], alphas[layer, block_tables],
            betas[layer, block_tables], Hkv))
    k = pages(k_codes, k_alphas, k_betas)
    v = pages(v_codes, v_alphas, v_betas)
    k = k.reshape(B, T * page, Hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, T * page, Hkv, hd).transpose(0, 2, 1, 3)
    return _paged_attend(q, k, v, ctx_lens, window=window, cap=cap)


def bcq_matmul_bitplane_ref(x, codes, alphas, betas, k_in: int):
    """Per-bitplane reassociation (G=1 only)."""
    assert alphas.shape[0] == 1
    signs = unpack_signs(codes, k_in)                    # (bits, K, N)
    xf = x.astype(jnp.float32)
    acc = jnp.zeros((*x.shape[:-1], codes.shape[-1]), jnp.float32)
    for i in range(codes.shape[0]):
        acc = acc + alphas[0, :, i] * jnp.einsum("...k,kn->...n", xf, signs[i])
    acc = acc + jnp.sum(xf, axis=-1, keepdims=True) * betas[0]
    return acc.astype(x.dtype)
