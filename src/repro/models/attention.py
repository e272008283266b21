"""Attention: GQA with qk-norm / sliding window / softcap, memory-bounded
chunked ("flash-style") full-sequence path, and single-token decode with a
KV cache (rolling buffer for sliding-window layers).

Shapes: activations (B, S, D); q/k/v (B, S, H, hd); caches (B, H, S, hd).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import init_linear, linear, rmsnorm, rope, softcap

NEG_INF = -1e30
# full-sequence attention switches to the chunked path above this length
CHUNKED_THRESHOLD = 2048
KV_CHUNK = 1024
# dry-run cost probes set this: XLA cost analysis counts while-loop bodies
# once, so probes unroll the kv-chunk scan (with coarser chunks)
FORCE_UNROLL = False


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def init_attn(cfg, key, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": init_linear(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": init_linear(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": init_linear(ks[3], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["qn"] = jnp.zeros((hd,), dtype)
        p["kn"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(cfg, p, x):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear(x, p["wq"])
    k = linear(x, p["wk"])
    v = linear(x, p["wv"])
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"], cfg.norm_eps)
        k = rmsnorm(k, p["kn"], cfg.norm_eps)
    return q, k, v


def _group_q(q, n_kv):
    """(B, S, H, d) -> (B, S, Hkv, rep, d). GQA is computed in grouped
    form — K/V are never materialized at H heads (a jnp.repeat here
    costs rep x cache bytes AND forces SPMD reshards; see EXPERIMENTS.md
    §Perf H1)."""
    B, S, H, d = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, d)


# --------------------------------------------------------------------------
# full-sequence attention (training / prefill)
# --------------------------------------------------------------------------

def _mask_bias(sq, skv, *, causal, window, q_offset=0, dtype=jnp.float32):
    """(sq, skv) additive bias. q position i attends kv position j iff
    (not causal or j <= i+q_offset) and (window is None or i+q_offset-j < window)."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= kj <= qi
    if window is not None:
        ok &= (qi - kj) < window
    return jnp.where(ok, 0.0, NEG_INF).astype(dtype)


def _attend_dense(q, k, v, *, causal, window, cap, scale):
    """Direct S x S attention (small sequences / oracle). Grouped GQA;
    v head dim may differ from q/k head dim (MLA)."""
    B, Sq, H, hd = q.shape
    dv = v.shape[-1]
    qg = _group_q(q, k.shape[2])                         # (B,Sq,Hkv,r,d)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32) * scale
    logits = softcap(logits, cap)
    logits = logits + _mask_bias(Sq, k.shape[1], causal=causal, window=window)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", w, v)
    return out.reshape(B, Sq, H, dv)


def _attend_chunked(q, k, v, *, causal, window, cap, scale):
    """Flash-style streaming over KV chunks: O(S * KV_CHUNK) live memory
    instead of O(S^2). Running (max, denom, acc) carried through a scan."""
    B, Sq, H, hd = q.shape
    Skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    rep = H // hkv
    qg = _group_q(q, hkv)                                # (B,Sq,Hkv,r,d)
    nc = -(-Skv // KV_CHUNK)
    pad = nc * KV_CHUNK - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nc, KV_CHUNK, hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, KV_CHUNK, hkv, dv).transpose(1, 0, 2, 3, 4)

    qi = jnp.arange(Sq)[:, None]

    def body(carry, inp):
        m, l, acc = carry
        ci, kb, vb = inp
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg,
                            kb).astype(jnp.float32) * scale
        logits = softcap(logits, cap)
        kj = ci * KV_CHUNK + jnp.arange(KV_CHUNK)[None, :]
        ok = kj < Skv
        if causal:
            ok = ok & (kj <= qi)
        if window is not None:
            ok = ok & ((qi - kj) < window)
        logits = jnp.where(ok[None, None, None], logits, NEG_INF)
        bm = jnp.maximum(m, jnp.max(logits, axis=-1))
        r = jnp.exp(m - bm)
        p = jnp.exp(logits - bm[..., None])
        l = l * r + jnp.sum(p, axis=-1)
        acc = acc * r[..., None] + jnp.einsum(
            "bhrqk,bkhd->bhrqd", p.astype(q.dtype), vb).astype(jnp.float32)
        return (bm, l, acc), None

    m0 = jnp.full((B, hkv, rep, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, hkv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, hkv, rep, Sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nc), kc, vc), unroll=FORCE_UNROLL)
    out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,hkv,r,Sq,dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dv).astype(q.dtype)


def attn_forward(cfg, spec, p, x, positions):
    """Full-sequence attention layer core (no residual/norm)."""
    q, k, v = _project_qkv(cfg, p, x)
    hd = cfg.resolved_head_dim
    if cfg.mla is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    scale = hd ** -0.5
    S = x.shape[1]
    fn = _attend_chunked if S > CHUNKED_THRESHOLD else _attend_dense
    out = fn(q, k, v, causal=cfg.causal, window=spec.window,
             cap=cfg.attn_softcap, scale=scale)
    out = out.reshape(*x.shape[:2], cfg.n_heads * hd)
    return linear(out, p["wo"])


# --------------------------------------------------------------------------
# decode (single new token, KV cache)
# --------------------------------------------------------------------------

def init_kv_cache(cfg, spec, batch, max_len, dtype):
    hd = cfg.resolved_head_dim
    S = max_len if spec.window is None else min(max_len, spec.window)
    shape = (batch, cfg.n_kv_heads, S, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv(cfg, n_pages, page_size, dtype, kv_bits=0,
                  kv_group_size=0):
    """Global page pool for one attention layer: every sequence's K/V
    pages live here; ownership is the block table's concern
    (serve/kv_cache.py). Page 0 is the allocator's null page.

    With `kv_bits > 0` pages store binary-coded K/V (quant/kv.py): sign
    bitplanes packed along head_dim plus per-(token, head, group) alpha/
    beta scales, quantized on-write by the decode/extend/scatter paths
    and expanded inside the attention kernels. The presence of the
    "k_codes" leaf is what selects the quantized path downstream."""
    hd = cfg.resolved_head_dim
    if not kv_bits:
        shape = (n_pages, page_size, cfg.n_kv_heads, hd)
        return {"k_pages": jnp.zeros(shape, dtype),
                "v_pages": jnp.zeros(shape, dtype)}
    from repro.quant.kv import kv_layout
    G, hdw = kv_layout(hd, kv_bits, kv_group_size)
    Hkv = cfg.n_kv_heads
    lead = (n_pages, page_size)
    pool = {}
    for side in ("k", "v"):
        # one lane-dense row per token (quant/kv.py:kv_pool_rows)
        pool[f"{side}_codes"] = jnp.zeros(lead + (Hkv * kv_bits * hdw,),
                                          jnp.uint32)
        pool[f"{side}_alphas"] = jnp.zeros(lead + (Hkv * G * kv_bits,),
                                           jnp.float32)
        pool[f"{side}_betas"] = jnp.zeros(lead + (Hkv * G,), jnp.float32)
    return pool


def paged_kv_page_bytes(cfg, page_size, dtype, kv_bits=0,
                        kv_group_size=0) -> int:
    """Device bytes one page id costs across the whole model: every
    attention layer (x the n_groups scan stack) holds a K and a V page
    of `page_size` tokens per KV head. The single owner of the
    bytes-per-page arithmetic (EngineStats, the capacity bench and the
    serve CLI all read it)."""
    from repro.quant.kv import kv_bytes_per_token_head
    itemsize = jnp.dtype(dtype or cfg.dtype).itemsize
    n_attn = sum(1 for s in cfg.pattern if s.kind == "attn") * cfg.n_groups
    if cfg.mla is not None:
        # latent pages: one compressed c_kv + one shared rotary key per
        # token — no per-head factor, no separate V page
        m = cfg.mla
        per_tok = (m.kv_lora_rank + m.qk_rope_head_dim) * itemsize
        return page_size * per_tok * n_attn
    per_vec = kv_bytes_per_token_head(cfg.resolved_head_dim, kv_bits,
                                      kv_group_size, itemsize)
    return 2 * page_size * cfg.n_kv_heads * per_vec * n_attn


def paged_kv_bits(cache) -> int:
    """kv_bits of a paged layer cache (0 = unquantized). The layout is
    self-describing: bits/groups are leaf shapes, so jit wrappers need
    no extra static arguments to dispatch."""
    if "k_codes" not in cache:
        return 0
    return cache["k_alphas"].shape[-1] // cache["k_betas"].shape[-1]


def _quant_scatter(cache, side, new, pid, off, mask=None):
    """Quantize-on-write: binary-code `new` K or V vectors (..., hd) and
    scatter codes+scales into the pool at (pid, off). With `mask`
    (matching new's leading dims), False rows re-write the null page's
    slot-0 content instead (the extend path's padding trick)."""
    from repro.quant.kv import kv_pool_rows, kv_quantize
    bits = paged_kv_bits(cache)
    G = cache[f"{side}_betas"].shape[-1] // new.shape[-2]
    gs = new.shape[-1] // G
    codes, alphas, betas = kv_pool_rows(*kv_quantize(new, bits, gs))
    out = dict(cache)
    for name, val in ((f"{side}_codes", codes),
                      (f"{side}_alphas", alphas),
                      (f"{side}_betas", betas)):
        pool = cache[name]
        if mask is not None:
            m = mask.reshape(mask.shape + (1,) * (val.ndim - mask.ndim))
            null = pool[0, 0].reshape(
                (1,) * mask.ndim + pool.shape[2:])
            val = jnp.where(m, val, null)
        out[name] = pool.at[pid, off].set(val.astype(pool.dtype))
    return out


def _gather_dequant(cache, side, block_tables, Hkv, hd):
    """Gather + expand a sequence's binary-coded pages:
    -> (B, T*page, Hkv, hd) fp32 (the extend path's dense view)."""
    from repro.quant.kv import kv_dequantize, kv_pool_views
    bt = block_tables
    B, T = bt.shape
    page = cache[f"{side}_codes"].shape[1]
    x = kv_dequantize(*kv_pool_views(cache[f"{side}_codes"][bt],
                                     cache[f"{side}_alphas"][bt],
                                     cache[f"{side}_betas"][bt], Hkv))
    return x.reshape(B, T * page, Hkv, hd)


def attn_decode_paged(cfg, spec, p, x, cache, block_tables, pos):
    """Single-token decode against a paged KV pool.

    x: (B, 1, D); cache: {"k_pages","v_pages"} (P, page, Hkv, hd) — or
    the binary-coded layout {"k_codes","k_alphas","k_betas","v_..."}
    (init_paged_kv(kv_bits=...)), where the new token's K/V is quantized
    before the scatter and the kernel dequantizes inside its accumulator
    loop; block_tables: (B, T) int32 page ids; pos: (B,) absolute
    positions. Writes the new K/V into page block_tables[b, pos//page]
    at offset pos%page, then attends over the sequence's gathered pages.
    Window layers mask by absolute position (no rolling buffer — pages
    beyond the window stay allocated; the scheduler may reclaim them
    later). Returns (y, cache)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(cfg, p, x)          # (B,1,H,hd)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)

    quant = paged_kv_bits(cache) > 0
    page = (cache["k_codes"] if quant else cache["k_pages"]).shape[1]
    b_idx = jnp.arange(B)
    pid = block_tables[b_idx, pos // page]
    off = pos % page
    if quant:
        cache = _quant_scatter(cache, "k", k[:, 0], pid, off)
        cache = _quant_scatter(cache, "v", v[:, 0], pid, off)
    else:
        kp, vp = cache["k_pages"], cache["v_pages"]
        kp = kp.at[pid, off].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[pid, off].set(v[:, 0].astype(vp.dtype))
        cache = {"k_pages": kp, "v_pages": vp}

    qg = q[:, 0].reshape(B, cfg.n_kv_heads,
                         cfg.n_heads // cfg.n_kv_heads, hd)
    # kernel on TPU, the jnp gather oracle elsewhere (the same fp32
    # masked softmax the dense attn_decode computes, so paged and dense
    # engines agree token-for-token on the fp32 CPU tests)
    from repro.kernels import ops
    out = ops.paged_decode(qg, cache, block_tables, pos + 1,
                           window=spec.window, cap=cfg.attn_softcap)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    y = linear(out, p["wo"])
    return y, cache


def attn_extend_paged(cfg, spec, p, h, cache, block_tables, start_pos,
                      chunk_mask):
    """Chunked-prefill step: C prompt tokens at absolute positions
    start_pos + [0..C) attend causally over everything already in the
    sequence's pages plus themselves. h: (B, C, D); chunk_mask: (B, C)
    bool — False marks padding tokens whose K/V must not land in pages.
    Returns (y, cache)."""
    B, C, _ = h.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(cfg, p, h)          # (B,C,H,hd)
    positions = start_pos[:, None] + jnp.arange(C)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    quant = paged_kv_bits(cache) > 0
    page = (cache["k_codes"] if quant else cache["k_pages"]).shape[1]
    pid = jnp.take_along_axis(block_tables, positions // page, axis=1)
    off = positions % page
    # masked scatter: padding tokens write to the null page (id 0) slot 0,
    # re-writing its current content (a no-op by construction)
    pid = jnp.where(chunk_mask, pid, 0)
    off = jnp.where(chunk_mask, off, 0)
    T = block_tables.shape[1]
    if quant:
        cache = _quant_scatter(cache, "k", k, pid, off, mask=chunk_mask)
        cache = _quant_scatter(cache, "v", v, pid, off, mask=chunk_mask)
        ck = _gather_dequant(cache, "k", block_tables, cfg.n_kv_heads, hd)
        cv = _gather_dequant(cache, "v", block_tables, cfg.n_kv_heads, hd)
    else:
        kp, vp = cache["k_pages"], cache["v_pages"]
        m4 = chunk_mask[:, :, None, None]
        kw = jnp.where(m4, k.astype(kp.dtype), kp[0, 0][None, None])
        vw = jnp.where(m4, v.astype(vp.dtype), vp[0, 0][None, None])
        kp = kp.at[pid, off].set(kw)
        vp = vp.at[pid, off].set(vw)
        cache = {"k_pages": kp, "v_pages": vp}
        ck = kp[block_tables].reshape(B, T * page, cfg.n_kv_heads, hd)
        cv = vp[block_tables].reshape(B, T * page, cfg.n_kv_heads, hd)
    ck = ck.transpose(0, 2, 1, 3)
    cv = cv.transpose(0, 2, 1, 3)
    qg = q.reshape(B, C, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, hd)
    logits = jnp.einsum("bqhrd,bhkd->bhrqk", qg,
                        ck.astype(q.dtype)).astype(jnp.float32) * hd ** -0.5
    logits = softcap(logits, cfg.attn_softcap)
    j = jnp.arange(T * page)[None, None, :]
    qi = positions[:, :, None]                  # (B, C, 1)
    ok = j <= qi
    if spec.window is not None:
        ok &= (qi - j) < spec.window
    logits = jnp.where(ok[:, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bhkd->bqhrd", w, cv.astype(q.dtype))
    out = out.reshape(B, C, cfg.n_heads * hd)
    y = linear(out, p["wo"])
    return y, cache


def attn_decode(cfg, spec, p, x, cache, pos):
    """x: (B, 1, D); pos: (B,) int32 absolute positions. Returns (y, cache).
    Sliding-window layers use a rolling buffer of size `window` indexed by
    pos % window."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(cfg, p, x)          # (B,1,H,hd)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)

    ck, cv = cache["k"], cache["v"]
    S = ck.shape[2]
    slot = pos if spec.window is None else pos % spec.window
    b_idx = jnp.arange(B)
    # k[:, 0] is (B, Hkv, hd); write each sample's new key at its slot.
    ck = ck.at[b_idx, :, slot].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[b_idx, :, slot].set(v[:, 0].astype(cv.dtype))

    # grouped GQA against the cache (B, Hkv, S, hd): no head repeat.
    qg = q[:, 0].reshape(B, cfg.n_kv_heads,
                         cfg.n_heads // cfg.n_kv_heads, hd)
    logits = jnp.einsum("bhrd,bhkd->bhrk", qg,
                        ck.astype(q.dtype)).astype(jnp.float32) * hd ** -0.5
    logits = softcap(logits, cfg.attn_softcap)
    # valid slots: for global layers j <= pos; for window layers the buffer
    # holds the last `window` positions -> slot j valid iff its absolute
    # position <= pos, i.e. filled (pos - window < abs_j <= pos).
    j = jnp.arange(S)[None, :]
    if spec.window is None:
        ok = j <= pos[:, None]
    else:
        ok = j < jnp.minimum(pos[:, None] + 1, spec.window)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrk,bhkd->bhrd", w, cv.astype(q.dtype))
    out = out.reshape(B, 1, cfg.n_heads * hd)
    y = linear(out, p["wo"])
    return y, {"k": ck, "v": cv}
