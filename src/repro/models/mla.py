"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

KV is compressed to a per-position latent c_kv (kv_lora_rank) plus a
shared rotary key k_pe (qk_rope_head_dim); the decode cache stores only
(latent, k_pe) — a large KV-memory reduction that compounds with GPTQT
weight quantization in the decode roofline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import init_linear, linear, rmsnorm, rope, softcap

NEG_INF = -1e30


def init_mla(cfg, key, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": init_linear(ks[0], d, m.q_lora_rank, dtype),
        "q_a_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": init_linear(ks[1], m.q_lora_rank, H * qk_hd, dtype),
        "wkv_a": init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_a_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "wkv_b": init_linear(ks[3], m.kv_lora_rank,
                             H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": init_linear(ks[4], H * m.v_head_dim, d, dtype),
    }


def _queries(cfg, p, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qa = rmsnorm(linear(x, p["wq_a"]), p["q_a_norm"], cfg.norm_eps)
    q = linear(qa, p["wq_b"])
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _latent(cfg, p, x, positions):
    m = cfg.mla
    kv = linear(x, p["wkv_a"])
    c_kv, k_pe = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_a_norm"], cfg.norm_eps)
    k_pe = rope(k_pe, positions, cfg.rope_theta)   # (B, S, rope_hd), shared
    return c_kv, k_pe


def _expand_kv(cfg, p, c_kv):
    m = cfg.mla
    B, S, _ = c_kv.shape
    H = cfg.n_heads
    kvb = linear(c_kv, p["wkv_b"])
    kvb = kvb.reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    return k_nope, v


def mla_forward(cfg, spec, p, x, positions):
    """Full-sequence MLA. For long sequences the score computation is
    routed through the shared chunked flash path using the concatenation
    identity [q_nope||q_pe]·[k_nope||k_pe] = q_nope·k_nope + q_pe·k_pe
    (k_pe broadcast across heads), so no S x S tensor is materialized."""
    from repro.models import attention as attn_mod

    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_pe = _queries(cfg, p, x, positions)
    c_kv, k_pe = _latent(cfg, p, x, positions)
    k_nope, v = _expand_kv(cfg, p, c_kv)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_cat = jnp.concatenate([q_nope, q_pe], axis=-1)     # (B,S,H,dn+dr)
    k_cat = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    fn = (attn_mod._attend_chunked if S > attn_mod.CHUNKED_THRESHOLD
          else attn_mod._attend_dense)
    out = fn(q_cat, k_cat, v, causal=cfg.causal, window=spec.window,
             cap=cfg.attn_softcap, scale=scale)          # (B,S,H,dv)
    out = out.reshape(B, S, H * m.v_head_dim)
    return linear(out, p["wo"])


def init_mla_cache(cfg, batch, max_len, dtype):
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}


def init_mla_paged(cfg, n_pages, page_size, dtype):
    """Global latent page pool for one MLA layer. Pages hold the
    compressed cache — one kv_lora_rank latent plus one shared rotary
    key per token, NOT per-head K/V — so a page costs
    page_size * (kv_lora_rank + qk_rope_head_dim) elements instead of
    2 * page_size * Hkv * hd. The singleton dim-2 axis keeps the leaves
    shaped like attention pools ((pages, page, heads, vec)) so
    map_page_leaves / copy_pages / compact treat them identically."""
    m = cfg.mla
    return {"ckv_pages": jnp.zeros((n_pages, page_size, 1,
                                    m.kv_lora_rank), dtype),
            "kpe_pages": jnp.zeros((n_pages, page_size, 1,
                                    m.qk_rope_head_dim), dtype)}


def _paged_latent_views(cache, block_tables):
    """Gather a sequence's latent pages into dense (B, T*page, ·) views."""
    B, T = block_tables.shape
    page = cache["ckv_pages"].shape[1]
    c_kv = cache["ckv_pages"][block_tables].reshape(B, T * page, -1)
    k_pe = cache["kpe_pages"][block_tables].reshape(B, T * page, -1)
    return c_kv, k_pe


def _mla_attend(cfg, p, q_nope, q_pe, c_kv, k_pe, ok):
    """Masked-softmax MLA attention over a dense latent view: absorb the
    up-projection (expand latents to K/V), score nope+rope parts, mask
    with `ok` (broadcastable to (B, 1, Sq, Skv)). Returns (B, Sq, D)."""
    m = cfg.mla
    B, Sq = q_nope.shape[:2]
    k_nope, v = _expand_kv(cfg, p, c_kv.astype(q_nope.dtype))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe,
                           k_pe.astype(q_nope.dtype)))
    logits = logits.astype(jnp.float32) * scale
    logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(ok, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q_nope.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    out = out.reshape(B, Sq, cfg.n_heads * m.v_head_dim)
    return linear(out, p["wo"])


def mla_decode_paged(cfg, spec, p, x, cache, block_tables, pos):
    """Single-token MLA decode against a latent page pool. Writes the
    new (c_kv, k_pe) into page block_tables[b, pos//page] at offset
    pos%page, then attends over the gathered latent pages with the
    up-projection absorbed the way attn_decode_paged expands raw pages.
    Same block-table/COW/null-page contract as attn_decode_paged."""
    B = x.shape[0]
    q_nope, q_pe = _queries(cfg, p, x, pos[:, None])     # (B,1,H,·)
    c_new, kpe_new = _latent(cfg, p, x, pos[:, None])    # (B,1,·)
    page = cache["ckv_pages"].shape[1]
    b_idx = jnp.arange(B)
    pid = block_tables[b_idx, pos // page]
    off = pos % page
    ckv = cache["ckv_pages"].at[pid, off, 0].set(
        c_new[:, 0].astype(cache["ckv_pages"].dtype))
    kpe = cache["kpe_pages"].at[pid, off, 0].set(
        kpe_new[:, 0].astype(cache["kpe_pages"].dtype))
    cache = {"ckv_pages": ckv, "kpe_pages": kpe}
    c_kv, k_pe = _paged_latent_views(cache, block_tables)
    S = c_kv.shape[1]
    ok = (jnp.arange(S)[None, :] <= pos[:, None])[:, None, None, :]
    y = _mla_attend(cfg, p, q_nope, q_pe, c_kv, k_pe, ok)
    return y, cache


def mla_extend_paged(cfg, spec, p, h, cache, block_tables, start_pos,
                     chunk_mask):
    """Chunked-prefill / verify step for MLA: C tokens at absolute
    positions start_pos + [0..C) write their latents into the
    sequence's pages (padding rows rewrite the null page's slot 0) and
    attend causally over pages + chunk. Mirrors attn_extend_paged."""
    B, C, _ = h.shape
    positions = start_pos[:, None] + jnp.arange(C)[None, :]
    q_nope, q_pe = _queries(cfg, p, h, positions)        # (B,C,H,·)
    c_new, kpe_new = _latent(cfg, p, h, positions)       # (B,C,·)
    page = cache["ckv_pages"].shape[1]
    pid = jnp.take_along_axis(block_tables, positions // page, axis=1)
    off = positions % page
    pid = jnp.where(chunk_mask, pid, 0)
    off = jnp.where(chunk_mask, off, 0)
    ckv, kpe = cache["ckv_pages"], cache["kpe_pages"]
    m3 = chunk_mask[:, :, None]
    cw = jnp.where(m3, c_new.astype(ckv.dtype), ckv[0, 0, 0][None, None])
    kw = jnp.where(m3, kpe_new.astype(kpe.dtype), kpe[0, 0, 0][None, None])
    cache = {"ckv_pages": ckv.at[pid, off, 0].set(cw),
             "kpe_pages": kpe.at[pid, off, 0].set(kw)}
    c_kv, k_pe = _paged_latent_views(cache, block_tables)
    S = c_kv.shape[1]
    ok = (jnp.arange(S)[None, :] <= positions[:, :, None])[:, None]
    y = _mla_attend(cfg, p, q_nope, q_pe, c_kv, k_pe, ok)
    return y, cache


def mla_decode(cfg, spec, p, x, cache, pos):
    m = cfg.mla
    B = x.shape[0]
    q_nope, q_pe = _queries(cfg, p, x, pos[:, None])     # (B,1,H,·)
    c_new, kpe_new = _latent(cfg, p, x, pos[:, None])    # (B,1,·)
    b_idx = jnp.arange(B)
    c_kv = cache["c_kv"].at[b_idx, pos].set(c_new[:, 0].astype(cache["c_kv"].dtype))
    k_pe = cache["k_pe"].at[b_idx, pos].set(kpe_new[:, 0].astype(cache["k_pe"].dtype))
    k_nope, v = _expand_kv(cfg, p, c_kv.astype(x.dtype))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe.astype(x.dtype)))
    logits = logits.astype(jnp.float32) * scale
    logits = softcap(logits, cfg.attn_softcap)
    S = c_kv.shape[1]
    ok = jnp.arange(S)[None, :] <= pos[:, None]
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    out = out.reshape(B, 1, cfg.n_heads * m.v_head_dim)
    y = linear(out, p["wo"])
    return y, {"c_kv": c_kv, "k_pe": k_pe}
