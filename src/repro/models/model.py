"""Model assembly: composable decoder/encoder stack driven by ModelConfig.

Layers are grouped into the config's repeating super-block ("pattern");
parameters for each pattern position are stacked along a leading
`n_groups` axis and the stack is traversed with `lax.scan`, keeping HLO
size (and compile time) independent of depth. Activation rematerialization
wraps the scan body (policy from cfg.remat).

Public entry points:
  init_params(cfg, key)            -> param pytree
  forward(cfg, params, inputs)     -> (logits, aux_loss)        [train]
  prefill(cfg, params, tokens, max_len) -> (last_logits, cache) [serve]
  init_cache(cfg, batch, max_len)  -> cache pytree
  decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mla as mla_mod
from repro.dist.context import constrain_batch
from repro.models.layers import (cross_entropy, init_linear, init_swiglu,
                                 linear, rmsnorm, softcap, swiglu)
from repro.models.moe import init_moe, moe_forward


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_layer(cfg, spec, key, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"ln": jnp.zeros((d,), dtype)}
    if spec.kind == "attn":
        if cfg.mla is not None:
            p["attn"] = mla_mod.init_mla(cfg, ks[0], dtype)
        else:
            p["attn"] = attn.init_attn(cfg, ks[0], dtype)
    else:
        p["mamba"] = mam.init_mamba(cfg, ks[0], dtype)
    if cfg.post_block_norms:
        p["post_ln"] = jnp.zeros((d,), dtype)
    if spec.mlp != "none":
        p["ln2"] = jnp.zeros((d,), dtype)
        if spec.mlp == "dense":
            p["mlp"] = init_swiglu(ks[1], d, cfg.d_ff, dtype)
        else:
            p["moe"] = init_moe(cfg, ks[1], dtype)
        if cfg.post_block_norms:
            p["post_ln2"] = jnp.zeros((d,), dtype)
    return p


def init_params(cfg, key, dtype=None):
    dtype = jnp.dtype(dtype or cfg.dtype)
    d = cfg.d_model
    k_embed, k_head, k_blocks = jax.random.split(key, 3)
    params = {"final_ln": jnp.zeros((d,), dtype)}
    if cfg.embed_input == "tokens":
        params["embed"] = (jax.random.normal(
            k_embed, (cfg.vocab_size, d), jnp.float32) * 0.02).astype(dtype)
    else:  # precomputed frame/patch embeddings -> learned input projection
        params["embed"] = init_linear(k_embed, d, d, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(k_head, d, cfg.vocab_size, dtype)

    blocks = {}
    gkeys = jax.random.split(k_blocks, cfg.n_groups)
    for i, spec in enumerate(cfg.pattern):
        init_one = functools.partial(_init_layer, cfg, spec, dtype=dtype)
        blocks[f"L{i}"] = jax.vmap(init_one)(
            jax.vmap(lambda k: jax.random.fold_in(k, i))(gkeys))
    params["blocks"] = blocks
    return params


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------

def embed_inputs(cfg, params, inputs):
    if cfg.embed_input == "tokens":
        return jnp.take(params["embed"], inputs, axis=0)
    return linear(inputs, params["embed"])


def unembed(cfg, params, x):
    """Logits in the activation dtype — the fp32 upcast happens inside
    the loss reductions (avoids materializing fp32 (B,S,V))."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    else:
        logits = linear(x, params["lm_head"])
    if logits.ndim == 3:       # anchor: batch on data, vocab on model
        logits = constrain_batch(logits, None, "model")
    return softcap(logits, cfg.final_softcap)


# --------------------------------------------------------------------------
# layer application
# --------------------------------------------------------------------------

def _apply_layer(cfg, spec, lp, x, positions, aux, *, collect_cache=False,
                 max_len=0):
    cache_out = None
    h = rmsnorm(x, lp["ln"], cfg.norm_eps)
    if spec.kind == "attn":
        if cfg.mla is not None:
            if collect_cache:
                y, cache_out = _mla_prefill(cfg, spec, lp["attn"], h,
                                            positions, max_len)
            else:
                y = mla_mod.mla_forward(cfg, spec, lp["attn"], h, positions)
        else:
            if collect_cache:
                y, cache_out = _attn_prefill(cfg, spec, lp["attn"], h,
                                             positions, max_len)
            else:
                y = attn.attn_forward(cfg, spec, lp["attn"], h, positions)
    else:
        if collect_cache:
            y, cache_out = mam.mamba_forward(cfg, lp["mamba"], h,
                                             return_state=True)
        else:
            y = mam.mamba_forward(cfg, lp["mamba"], h)
    if cfg.post_block_norms:
        y = rmsnorm(y, lp["post_ln"], cfg.norm_eps)
    x = x + y
    if spec.mlp != "none":
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if spec.mlp == "dense":
            y = swiglu(lp["mlp"], h)
        else:
            # prefill (collect_cache) uses the larger inference capacity
            cf = (cfg.moe.inference_capacity_factor if collect_cache
                  else cfg.moe.capacity_factor)
            y, a = moe_forward(cfg, lp["moe"], h, capacity_factor=cf)
            aux = aux + a
        if cfg.post_block_norms:
            y = rmsnorm(y, lp["post_ln2"], cfg.norm_eps)
        x = x + y
    return x, aux, cache_out


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save only inputs


# --------------------------------------------------------------------------
# training / scoring forward
# --------------------------------------------------------------------------

def forward(cfg, params, inputs, *, remat=None):
    """inputs: tokens (B, S) int32 or frames (B, S, D). -> (logits, aux)."""
    x = embed_inputs(cfg, params, inputs)
    S = x.shape[1]
    positions = jnp.arange(S)

    def body(carry, gp):
        x, aux = carry
        x = constrain_batch(x, None, None)   # anchor: batch on data axes
        for i, spec in enumerate(cfg.pattern):
            x, aux, _ = _apply_layer(cfg, spec, gp[f"L{i}"], x, positions, aux)
        x = constrain_batch(x, None, None)
        return (x, aux), None

    body = _remat(body, remat if remat is not None else cfg.remat)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"], unroll=cfg.scan_unroll)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return unembed(cfg, params, x), aux


def loss_fn(cfg, params, batch, *, aux_coef=0.01, remat=None):
    logits, aux = forward(cfg, params, batch["inputs"], remat=remat)
    loss = cross_entropy(logits, batch["labels"])
    return loss + aux_coef * aux, {"xent": loss, "aux": aux}


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------

def _attn_prefill(cfg, spec, p, h, positions, max_len):
    y = attn.attn_forward(cfg, spec, p, h, positions)
    q, k, v = attn._project_qkv(cfg, p, h)
    k = attn.rope(k, positions, cfg.rope_theta)
    S = h.shape[1]
    ck = k.transpose(0, 2, 1, 3)   # (B, Hkv, S, hd)
    cv = v.transpose(0, 2, 1, 3)
    if spec.window is None:
        pad = max_len - S
        ck = jnp.pad(ck, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cv = jnp.pad(cv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        w = min(spec.window, max_len)
        lo = max(0, S - w)
        slots = jnp.arange(lo, S) % w
        buf_k = jnp.zeros((ck.shape[0], ck.shape[1], w, ck.shape[3]), ck.dtype)
        buf_v = jnp.zeros_like(buf_k)
        ck = buf_k.at[:, :, slots].set(ck[:, :, lo:])
        cv = buf_v.at[:, :, slots].set(cv[:, :, lo:])
    return y, {"k": ck, "v": cv}


def _mla_prefill(cfg, spec, p, h, positions, max_len):
    y = mla_mod.mla_forward(cfg, spec, p, h, positions)
    c_kv, k_pe = mla_mod._latent(cfg, p, h, positions)
    pad = max_len - h.shape[1]
    c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
    k_pe = jnp.pad(k_pe, ((0, 0), (0, pad), (0, 0)))
    return y, {"c_kv": c_kv, "k_pe": k_pe}


def init_cache(cfg, batch, max_len, dtype=None):
    """Cache pytree mirroring params['blocks'] layout: leaf leading dim is
    n_groups (scanned together with the block stack)."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    cache = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.kind == "attn":
            if cfg.mla is not None:
                one = mla_mod.init_mla_cache(cfg, batch, max_len, dtype)
            else:
                one = attn.init_kv_cache(cfg, spec, batch, max_len, dtype)
        else:
            one = mam.init_mamba_cache(cfg, batch, dtype)
        cache[f"L{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape), one)
    return cache


def init_paged_cache(cfg, n_pages, page_size, max_seqs, dtype=None,
                     kv_bits=0, kv_group_size=0):
    """Paged cache pytree: attention layers get a global K/V page pool
    (n_pages, page_size, Hkv, hd) shared by all sequences; mamba layers
    keep per-slot constant-size state (max_seqs rows — recurrent state
    doesn't page). Same (n_groups,)-stacked layout as init_cache.

    `kv_bits > 0` stores pages binary-coded (quant/kv.py): packed sign
    bitplanes + per-(token, head, K-group) alpha/beta scale leaves
    instead of raw K/V — 4-8x fewer pool bytes per page at serving
    accuracy (see docs/SERVING.md §Quantized KV cache)."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    if cfg.mla is not None and kv_bits:
        raise NotImplementedError(
            "binary-coded pages code per-head K/V vectors; the MLA latent "
            "cache is already compressed and serves with kv_bits=0")
    cache = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.kind == "attn":
            if cfg.mla is not None:
                one = mla_mod.init_mla_paged(cfg, n_pages, page_size, dtype)
            else:
                one = attn.init_paged_kv(cfg, n_pages, page_size, dtype,
                                         kv_bits=kv_bits,
                                         kv_group_size=kv_group_size)
        else:
            one = mam.init_mamba_cache(cfg, max_seqs, dtype)
        cache[f"L{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape), one)
    return cache


# leaf names of the page pools (raw K/V, binary-coded K/V rows, MLA
# latents); every one has its page axis at dim 1 after the group stack.
# Per-slot recurrent (mamba) state is not among them.
PAGE_LEAVES = frozenset(
    f"{side}_{kind}" for side in ("k", "v")
    for kind in ("pages", "codes", "alphas", "betas")) | {"ckv_pages",
                                                         "kpe_pages"}


def map_page_leaves(fn, cache):
    """Apply fn to every page-pool leaf of a paged cache, leaving
    per-slot state alone."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(leaf) if path[-1].key in PAGE_LEAVES
        else leaf, cache)


def copy_pages(cache, src, dst, n_pages):
    """Copy-on-write fork: duplicate page src[i] -> dst[i] in every
    attention layer's K/V pool (paged-cache layout, page axis at dim 1
    after the group stack; mamba per-slot state is left alone). On a
    quantized pool the codes AND the alpha/beta scale leaves all copy —
    a fork that missed the scales would decode the old page's
    magnitudes under the new page's signs. src/dst are (n,) int32 page
    ids; (0, 0) pairs are harmless null-page no-ops, used by the engine
    to pad the copy list to a fixed trace shape."""
    return map_page_leaves(lambda leaf: leaf.at[:, dst].set(leaf[:, src]),
                           cache)


def _last_positions(x, last_pos):
    """x (B, S, D) -> (B, 1, D) at per-row index `last_pos` ((B,) int32),
    or the final position when last_pos is None (exact prompts)."""
    if last_pos is None:
        return x[:, -1:]
    idx = jnp.broadcast_to(last_pos[:, None, None],
                           (x.shape[0], 1, x.shape[2]))
    return jnp.take_along_axis(x, idx, axis=1)


def prefill(cfg, params, tokens, max_len, *, remat="none", last_pos=None):
    """Run the prompt, return (last-position logits, filled cache).
    `last_pos` ((B,) int32) selects the logits row for bucket-padded
    prompts (the engine pads prompt length to a power of two so the jit
    cache stays small; padding K/V slots are overwritten by later decode
    steps before they become visible to the causal mask)."""
    x = embed_inputs(cfg, params, tokens)
    S = x.shape[1]
    positions = jnp.arange(S)

    def body(carry, gp):
        x, aux = carry
        caches = {}
        for i, spec in enumerate(cfg.pattern):
            x, aux, caches[f"L{i}"] = _apply_layer(
                cfg, spec, gp[f"L{i}"], x, positions, aux,
                collect_cache=True, max_len=max_len)
        return (x, aux), caches

    body = _remat(body, remat)
    (x, _), cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 params["blocks"], unroll=cfg.scan_unroll)
    x = rmsnorm(_last_positions(x, last_pos), params["final_ln"],
                cfg.norm_eps)
    return unembed(cfg, params, x)[:, 0], cache


def _decode_scan(cfg, params, cache, x, attn_step):
    """Shared single-step decode machinery: scan the group stack, with
    the attention flavour injected (dense cache / paged pool / MLA)."""
    def body(x, inp):
        gp, gc = inp
        new_gc = {}
        for i, spec in enumerate(cfg.pattern):
            lp = gp[f"L{i}"]
            h = rmsnorm(x, lp["ln"], cfg.norm_eps)
            if spec.kind == "attn":
                y, new_gc[f"L{i}"] = attn_step(spec, lp["attn"], h,
                                               gc[f"L{i}"])
            else:
                y, new_gc[f"L{i}"] = mam.mamba_decode(
                    cfg, lp["mamba"], h, gc[f"L{i}"])
            if cfg.post_block_norms:
                y = rmsnorm(y, lp["post_ln"], cfg.norm_eps)
            x = x + y
            if spec.mlp != "none":
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                if spec.mlp == "dense":
                    y = swiglu(lp["mlp"], h)
                else:
                    # decode dispatch: 4x capacity slack instead of fully
                    # dropless (C=T) — C=T makes EVERY expert compute B
                    # tokens, inflating decode weight traffic E/k-fold
                    # (EXPERIMENTS.md §Perf iteration 2). At tiny T the
                    # min() keeps it exactly dropless (tests unaffected).
                    y, _ = moe_forward(cfg, lp["moe"], h, capacity_factor=4.0)
                if cfg.post_block_norms:
                    y = rmsnorm(y, lp["post_ln2"], cfg.norm_eps)
                x = x + y
        return x, new_gc

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache),
                                unroll=cfg.scan_unroll)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return unembed(cfg, params, x), new_cache


def decode_step(cfg, params, cache, tokens, pos):
    """One decode step. tokens: (B, 1) int32; pos: (B,) absolute positions.
    Returns (logits (B, V), new cache). Cache buffers are functionally
    updated; callers should donate them."""
    x = embed_inputs(cfg, params, tokens)
    if cfg.mla is not None:
        step = lambda spec, p, h, c: mla_mod.mla_decode(cfg, spec, p, h,
                                                        c, pos)
    else:
        step = lambda spec, p, h, c: attn.attn_decode(cfg, spec, p, h,
                                                      c, pos)
    logits, new_cache = _decode_scan(cfg, params, cache, x, step)
    return logits[:, 0], new_cache


def decode_step_paged(cfg, params, cache, tokens, pos, block_tables):
    """One decode step against a paged cache (init_paged_cache layout).
    block_tables: (B, T) int32 page ids, row b = sequence in slot b.
    Same contract as decode_step otherwise."""
    x = embed_inputs(cfg, params, tokens)
    if cfg.mla is not None:
        step = lambda spec, p, h, c: mla_mod.mla_decode_paged(
            cfg, spec, p, h, c, block_tables, pos)
    else:
        step = lambda spec, p, h, c: attn.attn_decode_paged(
            cfg, spec, p, h, c, block_tables, pos)
    logits, new_cache = _decode_scan(cfg, params, cache, x, step)
    return logits[:, 0], new_cache


def draft_propose_paged(cfg, params, cache, cur, base_pos, block_tables,
                        k_eff, null_row, k):
    """k greedy draft decode steps fused into ONE pass: the token
    feedback loop (argmax of step j feeds step j+1) runs on device, so
    a speculative tick costs one dispatch for all k proposals instead
    of k host round-trips with a logits transfer each. `k` is static
    (the unrolled step count); `k_eff` (B,) int32 clamps per-row depth —
    step j routes rows with k_eff <= j to `null_row`'s reserve page and
    position 0, exactly like any inactive decode row (their K/V writes
    land in the null page; their argmax feedback is computed but the
    caller ignores tokens past k_eff). Rows with k_eff == 0 never write
    anywhere real. Returns (draft tokens (B, k) int32, cache).

    Quantized draft weights are dequantized ONCE, before the step loop:
    at decode batch sizes the binary-code expansion (O(K*N*bits)) dwarfs
    the matmul it feeds (O(B*K*N)), and the k unrolled steps all consume
    the same weights — paying the expansion per step made propose cost
    ~k full draft decodes. The dense weights are trace-local workspace
    (alive only inside this dispatch), so the draft's zero-resident-HBM
    property is untouched: what persists is still just codes + re-fit
    scales."""
    is_qt = lambda l: hasattr(l, "dequant")
    params = jax.tree_util.tree_map(
        lambda l: l.dequant() if is_qt(l) else l, params, is_leaf=is_qt)
    toks = []
    for j in range(k):
        live_j = k_eff > j
        bt = jnp.where(live_j[:, None], block_tables, null_row[:, None])
        pos_j = jnp.where(live_j, base_pos + j, 0)
        logits, cache = decode_step_paged(cfg, params, cache,
                                          cur[:, None], pos_j, bt)
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(cur)
    return jnp.stack(toks, axis=1), cache


def _extend_scan(cfg, params, cache, tokens, start_pos, block_tables,
                 n_valid):
    """Shared multi-token paged pass: run C tokens (tokens (B, C) int32,
    padded; n_valid (B,) counts the real ones) at absolute positions
    start_pos + [0..C), writing their K/V into the sequences' pages and
    attending over pages + chunk causally. Returns logits at EVERY
    chunk position ((B, C, V), cache). Attention and MLA patterns only
    (recurrent mamba state needs sequential threading)."""
    if any(spec.kind != "attn" for spec in cfg.pattern):
        raise NotImplementedError(
            "multi-token paged passes require an attention-only pattern")
    C = tokens.shape[1]
    chunk_mask = jnp.arange(C)[None, :] < n_valid[:, None]
    x = embed_inputs(cfg, params, tokens)
    if cfg.mla is not None:
        step = lambda spec, p, h, c: mla_mod.mla_extend_paged(
            cfg, spec, p, h, c, block_tables, start_pos, chunk_mask)
    else:
        step = lambda spec, p, h, c: attn.attn_extend_paged(
            cfg, spec, p, h, c, block_tables, start_pos, chunk_mask)
    return _decode_scan(cfg, params, cache, x, step)


def extend_paged(cfg, params, cache, tokens, start_pos, block_tables,
                 n_valid):
    """Chunked prefill: _extend_scan reduced to the logits of the last
    valid chunk position ((B, V), cache) — all a prefill needs to seed
    its first decode token."""
    B, C = tokens.shape
    logits, new_cache = _extend_scan(cfg, params, cache, tokens,
                                     start_pos, block_tables, n_valid)
    idx = jnp.maximum(n_valid - 1, 0)[:, None, None]
    last = jnp.take_along_axis(
        logits, jnp.broadcast_to(idx, (B, 1, logits.shape[-1])), axis=1)
    return last[:, 0], new_cache


def verify_paged(cfg, params, cache, tokens, start_pos, block_tables,
                 n_valid):
    """Speculative verify: score C = k+1 positions in ONE batched pass
    and keep the logits at every position ((B, C, V), cache) — position
    j's row decides the fate of draft token j+1 (greedy acceptance:
    accept while draft token == argmax of the previous row). The pass
    also writes the TARGET's K/V for all C positions, overwriting
    whatever the draft speculatively wrote there — which is what makes
    greedy speculative decode token-identical to target-only decode
    regardless of the draft (serve/engine.py holds the accept/rollback
    logic)."""
    return _extend_scan(cfg, params, cache, tokens, start_pos,
                        block_tables, n_valid)


def scatter_prefill_cache(cfg, paged_cache, row_cache, slot, page_ids,
                          n_valid):
    """Merge one sequence's dense prefill cache (prefill() on a single
    padded row: attn leaves (G, 1, Hkv, S_pad, hd)) into the paged cache.
    page_ids: (S_pad // page_size,) int32 pages owned by the sequence;
    n_valid: true prompt length (padding K/V is masked out — pages only
    ever hold live tokens). Mamba state rows land at `slot`. On a
    binary-coded pool the dense prefill K/V is quantized page-by-page
    here (quantize-on-write), so pages never hold raw values."""
    out = {}
    for i, spec in enumerate(cfg.pattern):
        key = f"L{i}"
        pooled, row = paged_cache[key], row_cache[key]
        if spec.kind != "attn":
            out[key] = jax.tree.map(
                lambda pool, one: pool.at[:, slot].set(one[:, 0]),
                pooled, row)
            continue
        if "ckv_pages" in pooled:
            page = pooled["ckv_pages"].shape[2]
            npg = page_ids.shape[0]

            def put_latent(pool, one):
                # one (G, 1, S_pad, r) -> (G, npg, page, 1, r)
                G, _, S_pad, r = one.shape
                rows = one[:, 0]
                pad = npg * page - S_pad
                if pad:
                    rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
                rows = rows.reshape(G, npg, page, 1, r)
                keep = (jnp.arange(npg * page) < n_valid).reshape(npg, page)
                cur = pool[:, page_ids]
                return pool.at[:, page_ids].set(
                    jnp.where(keep[None, :, :, None, None],
                              rows.astype(pool.dtype), cur))

            out[key] = {
                "ckv_pages": put_latent(pooled["ckv_pages"], row["c_kv"]),
                "kpe_pages": put_latent(pooled["kpe_pages"], row["k_pe"])}
            continue
        quant = "k_codes" in pooled
        page = (pooled["k_codes"] if quant else pooled["k_pages"]).shape[2]
        npg = page_ids.shape[0]

        def paged_rows(one):
            # one (G, 1, Hkv, S_pad, hd) -> (G, npg, page, Hkv, hd)
            G, _, Hkv, S_pad, hd = one.shape
            r = one[:, 0].transpose(0, 2, 1, 3)            # (G,S_pad,Hkv,hd)
            pad = npg * page - S_pad
            if pad:
                r = jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return r.reshape(G, npg, page, Hkv, hd)

        if quant:
            from repro.quant.kv import kv_pool_rows, kv_quantize

            bits = attn.paged_kv_bits(pooled)
            Gk = pooled["k_betas"].shape[-1] // cfg.n_kv_heads

            def put_q(side, one):
                hd = one.shape[-1]
                r = paged_rows(one)
                vals = kv_pool_rows(*kv_quantize(r, bits, hd // Gk))
                keep = (jnp.arange(npg * page) < n_valid).reshape(npg, page)
                leaves = {}
                for suffix, val in zip(("codes", "alphas", "betas"), vals):
                    pool = pooled[f"{side}_{suffix}"]
                    km = keep.reshape(1, npg, page, 1)
                    cur = pool[:, page_ids]
                    leaves[f"{side}_{suffix}"] = pool.at[:, page_ids].set(
                        jnp.where(km, val.astype(pool.dtype), cur))
                return leaves

            out[key] = {**put_q("k", row["k"]), **put_q("v", row["v"])}
            continue

        def put(pool, one):
            r = paged_rows(one)
            keep = (jnp.arange(npg * page) < n_valid).reshape(npg, page)
            cur = pool[:, page_ids]
            return pool.at[:, page_ids].set(
                jnp.where(keep[None, :, :, None, None], r, cur))

        out[key] = {"k_pages": put(pooled["k_pages"], row["k"]),
                    "v_pages": put(pooled["v_pages"], row["v"])}
    return out
