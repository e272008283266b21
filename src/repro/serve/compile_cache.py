"""Process-wide cache of the serving jit wrappers, keyed by
(wrapper kind, model config, mesh fingerprint).

Before this existed every ServeEngine built its own `jax.jit` closures,
so each engine owned a private XLA compilation cache: N engines (or N
constructions of the same engine after a restart of the serving loop)
paid N warmups for byte-identical programs. Engines now *borrow* the
jitted callable from here — the first engine traces and compiles, every
later engine with the same config and mesh reuses the compiled steps
outright (`jax.jit` keys executables by argument shapes/shardings, so
distinct batch shapes still compile independently inside one entry).

Keying rules:
  - `cfg` is the frozen ModelConfig (hashable); wrappers close over it,
    so it must be part of the key. Pass None for config-independent
    wrappers (page copies, block-table scatter).
  - the mesh participates via `mesh_fingerprint` (axis names, shape and
    device ids): traces capture sharding constraints from the active
    mesh context, so callables are never shared across meshes. None
    (single-device serving) is its own key.

`stats()` exposes hit/miss counters; tests assert that constructing a
second engine adds zero entries and that its runs add zero XLA
compilations (`jitted._cache_size()` is flat).

`enable_persistent_cache()` is the on-disk side: XLA executables
survive the process, so a relaunch skips recompiling the same steps.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import (copy_pages, decode_step, decode_step_paged,
                                draft_propose_paged, extend_paged, forward,
                                prefill, scatter_prefill_cache, verify_paged)

_CACHE: dict = {}
_STATS = {"hits": 0, "misses": 0}
# fixed, git-ignored home of the persistent cache inside the checkout:
# the directory is part of each entry's key, so it never moves
PERSISTENT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile; returns its directory. Where JAX_COMPILATION_CACHE_DIR is
    set, JAX already keeps the cache there and nothing else is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(PERSISTENT_DIR))
    return str(PERSISTENT_DIR)


def mesh_fingerprint(mesh):
    """Hashable identity of a mesh: axis names, shape, device ids."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(np.shape(mesh.devices)),
            tuple(int(d.id) for d in np.ravel(mesh.devices)))


def _build(kind, cfg):
    if kind == "decode_dense":
        return jax.jit(lambda p, c, t, s: decode_step(cfg, p, c, t, s),
                       donate_argnums=(1,))
    if kind == "decode_paged":
        def step(p, c, t, s, bt, live, null_row):
            # masked (inactive) rows write to their shard's reserve page
            # instead of block-table garbage; null_row is all zeros for
            # unsharded pools (the classic `bt * live` null-page trick)
            bt = jnp.where(live[:, None] > 0, bt, null_row[:, None])
            return decode_step_paged(cfg, p, c, t, s, bt)
        return jax.jit(step, donate_argnums=(1,))
    if kind == "prefill":
        return jax.jit(
            lambda p, t, lp, ml: prefill(cfg, p, t, ml, last_pos=lp),
            static_argnums=(3,))
    if kind == "extend_paged":
        return jax.jit(
            lambda p, c, t, sp, bt, nv: extend_paged(cfg, p, c, t, sp,
                                                     bt, nv),
            donate_argnums=(1,))
    if kind == "draft_propose":
        # the draft's k-step propose pass (fused argmax feedback loop;
        # see models/model.py:draft_propose_paged). A separate kind from
        # decode_paged keeps warmup/hit accounting per role honest; the
        # draft params' smaller alpha shapes would key separate
        # executables anyway. k (the unroll depth) is static — one
        # executable per distinct speculation depth.
        def propose(p, c, cur, sp, bt, ke, null_row, k):
            return draft_propose_paged(cfg, p, c, cur, sp, bt, ke,
                                       null_row, k)
        return jax.jit(propose, donate_argnums=(1,), static_argnums=(7,))
    if kind == "verify_paged":
        # speculative verify: k+1 positions in one pass, logits kept at
        # EVERY position (k is keyed implicitly by the token width —
        # jax.jit compiles one executable per distinct k+1)
        def verify_step(p, c, t, sp, bt, nv, live, null_row):
            bt = jnp.where(live[:, None] > 0, bt, null_row[:, None])
            return verify_paged(cfg, p, c, t, sp, bt, nv)
        return jax.jit(verify_step, donate_argnums=(1,))
    if kind == "scatter_prefill":
        return jax.jit(
            lambda c, r, sl, pi, nv: scatter_prefill_cache(cfg, c, r, sl,
                                                           pi, nv),
            donate_argnums=(0,))
    if kind == "copy_pages":
        return jax.jit(copy_pages, donate_argnums=(0,),
                       static_argnums=(3,))
    if kind == "bt_update":
        return jax.jit(lambda bt, idx, rows: bt.at[idx].set(rows),
                       donate_argnums=(0,))
    if kind == "eval_forward":
        # logits-only forward for perplexity eval (data/evaluate.py):
        # repeated evals of the same config — the GPTQ sweeps run dozens
        # — share one trace instead of re-jitting per perplexity() call
        return jax.jit(lambda p, x: forward(cfg, p, x)[0])
    raise KeyError(kind)


def get(kind: str, cfg=None, mesh=None):
    """The shared jitted wrapper for (kind, cfg, mesh) — built on first
    request, borrowed ever after."""
    key = (kind, cfg, mesh_fingerprint(mesh))
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = _build(kind, cfg)
        _STATS["misses"] += 1
    else:
        _STATS["hits"] += 1
    return fn


def stats() -> dict:
    return {"entries": len(_CACHE), **_STATS}


def clear() -> None:
    """Drop every cached wrapper (tests isolating warmup accounting)."""
    _CACHE.clear()
    _STATS.update(hits=0, misses=0)
