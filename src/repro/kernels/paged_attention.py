"""Pallas TPU kernel: batched single-token paged-attention decode.

K/V live in a global page pool `(n_pages, page_size, Hkv, hd)` shared by
every sequence; each sequence owns a row of a block table `(B, T)` of
page ids (see serve/kv_cache.py). The grid is (batch, pages-per-seq):
for each sequence the kernel streams its pages HBM->VMEM one per grid
step — the page id comes from the *scalar-prefetched* block table, so
the DMA address is known before the body runs — and folds each page
into an online-softmax (flash) accumulator held in VMEM scratch. One
grid row therefore reads exactly ctx_len tokens of K/V instead of a
dense max_len slab, which is what makes decode bandwidth scale with the
*live* tokens (the same argument as the BCQ weight kernel: decode is
bandwidth-bound, so bytes moved == time).

Unused block-table slots MUST hold a valid page id (the allocator keeps
them 0 and reserves page 0 as a never-allocated null page); the kernel
masks their contribution by token index, not by page id.

`kernels/ops.py:paged_decode` dispatches here on TPU and runs it in
interpret mode off-TPU when forced; `ref.py` holds the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw import WORD

NEG_INF = -1e30

def _fold(t, ctx, q, k, v, m_ref, l_ref, acc_ref, *, page_size, scale,
          window, cap, kv_axes="phd"):
    """Fold one page of fp32 K/V into the flash accumulator scratch.
    q (Hkv, rep, hd); k/v (page, Hkv, hd), or (Hkv, page, hd) with
    kv_axes="hpd"."""
    logits = jnp.einsum(f"hrd,{kv_axes}->hrp", q, k,
                        preferred_element_type=jnp.float32) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    j = t * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    ok = j < ctx
    if window is not None:
        ok &= (ctx - 1 - j) < window
    logits = jnp.where(ok, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    r = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_ref[...] = l_ref[...] * r + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * r[..., None] + jnp.einsum(
        f"hrp,{kv_axes}->hrd", p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, page_size: int, pages_per_seq: int, scale: float,
            window, cap):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = cl_ref[b]

    @pl.when(t * page_size < ctx)
    def _fold_page():
        _fold(t, ctx, q_ref[0].astype(jnp.float32),
              k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
              m_ref, l_ref, acc_ref, page_size=page_size, scale=scale,
              window=window, cap=cap)

    @pl.when(t == pages_per_seq - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _expand_page(codes, alphas, betas, *, n_kv_heads: int, hd: int):
    """VMEM dequant of one binary-coded page (the bcq_matmul expand,
    re-oriented for the KV layout). Pool rows (quant/kv.py:kv_pool_rows):
    codes (page, Hkv*bits*hd/32) u32, alphas (page, Hkv*G*bits), betas
    (page, Hkv*G) -> fp32 (Hkv, page, hd). Each head vector is built
    from single-lane slices spread over the head_dim lanes with selects
    (Mosaic lowers no 3-D gather): lane d reads word d // 32, bit
    d % 32, and the scales of group d // gs."""
    page = codes.shape[0]
    G = betas.shape[-1] // n_kv_heads
    bits = alphas.shape[-1] // betas.shape[-1]
    hdw = hd // WORD
    gs = hd // G
    alphas = alphas.astype(jnp.float32)
    betas = betas.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (page, hd), 1)
    shift = (lane % WORD).astype(jnp.uint32)

    def spread(v, base, n, width):
        """Lane d of the (page, hd) result takes v[:, base + d // width]."""
        out = jnp.broadcast_to(v[:, base:base + 1], (page, hd))
        for w in range(1, n):
            out = jnp.where(lane // width == w, v[:, base + w:base + w + 1],
                            out)
        return out

    heads = []
    for h in range(n_kv_heads):
        acc = spread(betas, h * G, G, gs)
        for i in range(bits):
            words = spread(codes, (h * bits + i) * hdw, hdw, WORD)
            # select, not cast: Mosaic has no uint32 -> float32 conversion
            on = ((words >> shift) & jnp.uint32(1)) == jnp.uint32(1)
            a = jnp.broadcast_to(alphas[:, h * G * bits + i:
                                        h * G * bits + i + 1], (page, hd))
            for g in range(1, G):
                c = (h * G + g) * bits + i
                a = jnp.where(lane // gs == g, alphas[:, c:c + 1], a)
            acc = acc + jnp.where(on, a, -a)
        heads.append(acc)
    return jnp.stack(heads)


def _kernel_quant(bt_ref, cl_ref, q_ref, kc_ref, ka_ref, kb_ref, vc_ref,
                  va_ref, vb_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  page_size: int, pages_per_seq: int, scale: float,
                  window, cap, hd: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = cl_ref[b]

    @pl.when(t * page_size < ctx)
    def _fold_page():
        Hkv = q_ref.shape[1]
        k = _expand_page(kc_ref[0], ka_ref[0], kb_ref[0], n_kv_heads=Hkv,
                         hd=hd)
        v = _expand_page(vc_ref[0], va_ref[0], vb_ref[0], n_kv_heads=Hkv,
                         hd=hd)
        _fold(t, ctx, q_ref[0].astype(jnp.float32), k, v,
              m_ref, l_ref, acc_ref, page_size=page_size, scale=scale,
              window=window, cap=cap, kv_axes="hpd")

    @pl.when(t == pages_per_seq - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "cap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    window=None, cap=None, interpret=False):
    """q (B, Hkv, rep, hd); k_pages/v_pages (P, page_size, Hkv, hd);
    block_tables (B, T) int32 page ids; ctx_lens (B,) int32 live tokens
    per sequence (including the token just written). Returns
    (B, Hkv, rep, hd) in q.dtype."""
    B, Hkv, rep, hd = q.shape
    _, page_size, _, _ = k_pages.shape
    T = block_tables.shape[1]
    scale = hd ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, Hkv, rep, hd),
                         lambda b, t, bt, cl: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, hd),
                         lambda b, t, bt, cl: (bt[b, t], 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, hd),
                         lambda b, t, bt, cl: (bt[b, t], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rep, hd),
                               lambda b, t, bt, cl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep), jnp.float32),       # running max
            pltpu.VMEM((Hkv, rep), jnp.float32),       # running denom
            pltpu.VMEM((Hkv, rep, hd), jnp.float32),   # weighted acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, pages_per_seq=T,
                          scale=scale, window=window, cap=cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables, ctx_lens, q, k_pages, v_pages)


@functools.partial(jax.jit,
                   static_argnames=("window", "cap", "interpret"))
def paged_attention_quant(q, k_codes, k_alphas, k_betas, v_codes,
                          v_alphas, v_betas, block_tables, ctx_lens, *,
                          window=None, cap=None, interpret=False):
    """Fused-dequant paged decode over a binary-coded page pool
    (quant/kv.py pool rows): q (B, Hkv, rep, hd); codes
    (P, page, Hkv*bits*hd/32) u32; alphas (P, page, Hkv*G*bits); betas
    (P, page, Hkv*G); block_tables (B, T); ctx_lens (B,).

    Same grid/flash structure as `paged_attention`, but each grid step
    streams a page's *codes + scales* HBM->VMEM (bits/8 + scale bytes
    per entry instead of 2-4) and expands them to fp32 inside the
    accumulator loop — the bcq_matmul fusion argument applied to the KV
    pool: decode is bandwidth-bound, so shrinking the pages shrinks the
    time. Returns (B, Hkv, rep, hd) in q.dtype."""
    B, Hkv, rep, hd = q.shape
    page_size = k_codes.shape[1]
    T = block_tables.shape[1]
    scale = hd ** -0.5

    def page_spec(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda b, t, bt, cl:
                            (bt[b, t],) + (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, Hkv, rep, hd),
                         lambda b, t, bt, cl: (b, 0, 0, 0)),
            *(page_spec(a.shape[1:]) for a in (k_codes, k_alphas, k_betas,
                                               v_codes, v_alphas, v_betas)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rep, hd),
                               lambda b, t, bt, cl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep), jnp.float32),       # running max
            pltpu.VMEM((Hkv, rep), jnp.float32),       # running denom
            pltpu.VMEM((Hkv, rep, hd), jnp.float32),   # weighted acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel_quant, page_size=page_size,
                          pages_per_seq=T, scale=scale, window=window,
                          cap=cap, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables, ctx_lens, q, k_codes, k_alphas, k_betas,
      v_codes, v_alphas, v_betas)
