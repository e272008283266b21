"""GSPMD sharding rules for params, optimizer state, caches and inputs.

One rule set, three consumers: the training launcher, the dry-run
compiler, and the serving path. Rules are *total* functions of
(config, tree path, leaf shape, mesh) with a divisibility guard — an
axis is only applied when the dim is divisible by the mesh axis size,
otherwise it is dropped (replicated) rather than erroring.

Conventions (2-axis production mesh ("data", "model")):
  - "expand" projections (wq/wk/wv/wg/wu/...):  K on data (FSDP), N on model
  - "contract" projections (wo/wd/out_proj):    K on model, N on data
  - embed (V, D): vocab on model, d_model on data; lm_head transposed
  - MoE expert stacks (G, E, K, N): experts on model when E % model == 0
    (expert parallelism), else TP inside each expert
  - KV caches (G, B, H, S, hd): batch on data; heads on model when
    divisible, else *sequence* on model (flash-decode partial softmax);
    B=1 shards sequence over both axes
  - paged KV pools (G, P, page, H, hd): pages on data, heads on model
  - QuantizedTensor leaves shard like the dense weight they replace
    (codes: K on data / N on model; alphas/betas: N on model)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# projections whose *input* dim carries the model axis (output of a
# model-sharded matmul feeds them; avoids a reshard between the pair)
_CONTRACT = {"wo", "wd", "out_proj"}
# matmul-weight leaves (everything else — norms, biases, conv filters,
# SSM decay params — replicates): any name starting with "w" plus these
_MATMUL_EXTRA = {"in_proj", "x_proj", "dt_w", "out_proj", "router",
                 "embed", "lm_head"}
_QT_LEAVES = {".codes", ".alphas", ".betas"}
# Leaves models/ constructs that intentionally replicate (norm scales,
# per-channel vectors, SSM decay params). repro-lint rule R006 checks
# every leaf name models/ constructs against this module: a new leaf
# must either match a placement rule below or be declared here, so
# replication is always a decision, never a silent default.
REPLICATED_LEAVES = frozenset({
    "ln", "ln2", "post_ln", "post_ln2", "final_ln",   # rmsnorm scales
    "qn", "kn", "q_a_norm", "kv_a_norm",              # qk / latent norms
    "conv_w", "conv_b", "dt_b", "A_log", "D",         # mamba per-channel
})


def _is_matmul(name: str) -> bool:
    return name.startswith("w") or name in _MATMUL_EXTRA


# --------------------------------------------------------------------------
# mesh helpers
# --------------------------------------------------------------------------

def mesh_axis_sizes(mesh) -> dict:
    """axis name -> size. Works for jax.sharding.Mesh AND shape-only
    stand-ins that expose .axis_names and .devices (tests use a
    FakeMesh). THE one derivation — engine/launcher shard counts must
    not re-zip this themselves."""
    return dict(zip(tuple(mesh.axis_names), np.shape(mesh.devices)))


_axis_sizes = mesh_axis_sizes


def _div(n: int, axis, sizes) -> bool:
    if axis is None:
        return True
    if isinstance(axis, tuple):
        total = 1
        for a in axis:
            total *= sizes[a]
        return n % total == 0
    return n % sizes[axis] == 0


def _guard(shape, spec, sizes):
    return P(*[a if _div(d, a, sizes) else None for d, a in zip(shape, spec)])


def batch_pspec(mesh, batch: int, rest=(None,)) -> P:
    """Batch-dim spec: all data-ish axes when divisible, the plain data
    axis as fallback, replicated otherwise. `rest` fills trailing dims."""
    sizes = _axis_sizes(mesh)
    combo = tuple(a for a in ("pod", "data") if a in sizes)
    ax = None
    if combo and _div(batch, combo, sizes):
        ax = combo if len(combo) > 1 else combo[0]
    elif "data" in sizes and _div(batch, "data", sizes):
        ax = "data"
    return P(ax, *rest)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def _path_names(path):
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append("." + str(k.name))
        else:
            out.append(str(k))
    return out


def param_pspec(cfg, path, leaf, mesh, *, fsdp: bool = True) -> P:
    """Sharding rule for one parameter leaf. `path` is a jax key path."""
    return named_pspec(cfg, _path_names(path), leaf, mesh, fsdp=fsdp)


def named_pspec(cfg, names, leaf, mesh, *, fsdp: bool = True) -> P:
    """param_pspec over plain string path components — the manifest
    writer (ckpt/packed.py) walks a nested dict and has no jax key
    paths. QuantizedTensor children are addressed by appending
    ".codes"/".alphas"/".betas" to the weight's path."""
    sizes = _axis_sizes(mesh)
    name = names[-1]
    shape = tuple(leaf.shape)
    data_ax = "data" if (fsdp and "data" in sizes) else None
    model_ax = "model" if "model" in sizes else None

    if name in _QT_LEAVES:
        return _qt_pspec(name, names[-2] if len(names) > 1 else "", shape,
                         sizes, data_ax, model_ax,
                         is_expert=any(n == "moe" for n in names))

    if len(shape) < 2 or not _is_matmul(name):
        return P(*([None] * len(shape)))

    if name == "embed" and len(shape) == 2:
        return _guard(shape, P(model_ax, data_ax), sizes)
    if name == "lm_head":
        return _guard(shape, (None,) * (len(shape) - 2) + (data_ax, model_ax),
                      sizes)

    is_expert = any(n == "moe" for n in names) and len(shape) >= 3 \
        and name != "router"
    if is_expert:
        lead = (None,) * (len(shape) - 3)
        E, K, N = shape[-3:]
        if model_ax is not None and sizes[model_ax] and E % sizes[model_ax] == 0:
            # expert parallelism: E on model, FSDP on K, N replicated
            return _guard(shape, lead + (model_ax, data_ax, None), sizes)
        if name in _CONTRACT:
            return _guard(shape, lead + (None, model_ax, data_ax), sizes)
        return _guard(shape, lead + (None, data_ax, model_ax), sizes)

    lead = (None,) * (len(shape) - 2)
    if name in _CONTRACT:
        return _guard(shape, lead + (model_ax, data_ax), sizes)
    return _guard(shape, lead + (data_ax, model_ax), sizes)


def _qt_pspec(leaf_name, base_name, shape, sizes, data_ax, model_ax,
              is_expert=False):
    """QuantizedTensor children shard like the dense weight they stand
    in for: codes (..., bits, K/32, N), alphas (..., G, N, bits),
    betas (..., G, N). Batched-expert stacks (leading E dim under a
    "moe" path) mirror the dense expert-parallel rule: E rides the
    model axis when divisible, codes keep FSDP on the packed-K dim and
    scales replicate within an expert."""
    if base_name in _CONTRACT:
        k_ax, n_ax = model_ax, data_ax
    else:
        k_ax, n_ax = data_ax, model_ax
    base_rank = {".codes": 3, ".alphas": 3, ".betas": 2}[leaf_name]
    if (is_expert and base_name != "router" and len(shape) > base_rank
            and model_ax is not None and shape[0] % sizes[model_ax] == 0):
        mid = (None,) * (len(shape) - base_rank - 1)
        if leaf_name == ".codes":
            spec = (model_ax,) + mid + (None, data_ax, None)
        elif leaf_name == ".alphas":
            spec = (model_ax,) + mid + (None, None, None)
        else:  # .betas
            spec = (model_ax,) + mid + (None, None)
        return _guard(shape, spec, sizes)
    if leaf_name == ".codes":
        spec = (None,) * (len(shape) - 2) + (k_ax, n_ax)
    elif leaf_name == ".alphas":
        spec = (None,) * (len(shape) - 2) + (n_ax, None)
    else:  # .betas
        spec = (None,) * (len(shape) - 1) + (n_ax,)
    return _guard(shape, spec, sizes)


def params_shardings(cfg, params, mesh, *, fsdp: bool = True):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_pspec(cfg, p, l, mesh,
                                                     fsdp=fsdp)), params)


def opt_state_shardings(cfg, opt_state, mesh, *, fsdp: bool = True):
    """Optimizer moments mirror the param rules (path minus the mu/nu/
    master prefix); scalars (step) replicate."""
    def rule(path, leaf):
        if len(leaf.shape) == 0:
            return NamedSharding(mesh, P())
        sub = path[1:] if len(path) > 1 else path
        return NamedSharding(mesh, param_pspec(cfg, sub, leaf, mesh,
                                               fsdp=fsdp))
    return jax.tree_util.tree_map_with_path(rule, opt_state)


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def cache_pspec(cfg, path, leaf, mesh) -> P:
    sizes = _axis_sizes(mesh)
    names = _path_names(path)
    name = names[-1]
    shape = tuple(leaf.shape)
    data_ax = "data" if "data" in sizes else None
    model_ax = "model" if "model" in sizes else None

    if name in ("k_pages", "v_pages") and len(shape) == 5:
        # (G, P, page, H, hd): pages across data, kv heads across model
        return _guard(shape, P(None, data_ax, None, model_ax, None), sizes)

    if name in ("ckv_pages", "kpe_pages") and len(shape) == 5:
        # MLA latent pages (G, P, page, 1, r): pages across data; the
        # per-token latent/rope vectors are small and replicate
        return _guard(shape, P(None, data_ax, None, None, None), sizes)

    # binary-coded pool rows (quant/kv.py:kv_pool_rows, heads
    # outermost): same placement — pages ride the data axis, kv heads
    # the model axis — applied to the codes and both scale leaves so a
    # page's codes and scales always land on the same devices. The row
    # splits on the model axis only where whole heads do: a width that
    # divides while the heads do not would cut a head's codes apart
    if name in ("k_codes", "v_codes", "k_alphas", "v_alphas", "k_betas",
                "v_betas") and len(shape) == 4:
        # (G, P, page, H*bits*hd/32) / (G, P, page, H*Gk*bits) /
        # (G, P, page, H*Gk)
        head_ax = model_ax if _div(cfg.n_kv_heads, model_ax, sizes) \
            else None
        return _guard(shape, P(None, data_ax, None, head_ax), sizes)

    if name in ("k", "v") and len(shape) == 5:
        G, B, H, S, hd = shape
        batch_ax = data_ax if _div(B, data_ax, sizes) else None
        head_ax = model_ax if _div(H, model_ax, sizes) else None
        seq_ax = None
        if head_ax is None and model_ax is not None:
            both = tuple(a for a in (data_ax, model_ax) if a)
            if batch_ax is None and len(both) > 1 and _div(S, both, sizes):
                seq_ax = both
            elif _div(S, model_ax, sizes):
                seq_ax = model_ax
        return P(None, batch_ax, head_ax, seq_ax, None)

    if name in ("c_kv", "k_pe") and len(shape) == 4:   # MLA latent cache
        G, B, S, r = shape
        batch_ax = data_ax if _div(B, data_ax, sizes) else None
        seq_ax = model_ax if _div(S, model_ax, sizes) else None
        return P(None, batch_ax, seq_ax, None)

    if name in ("ssm", "conv") and len(shape) >= 3:    # mamba state
        batch_ax = data_ax if _div(shape[1], data_ax, sizes) else None
        spec = [None, batch_ax] + [None] * (len(shape) - 2)
        # d_inner rides the model axis when divisible (last dim for conv,
        # dim 2 for ssm)
        di_dim = 2 if name == "ssm" else len(shape) - 1
        if _div(shape[di_dim], model_ax, sizes):
            spec[di_dim] = model_ax
        return P(*spec)

    # unknown cache leaf: batch on data when it looks batched, else repl.
    if len(shape) >= 2 and _div(shape[1], data_ax, sizes):
        return P(None, data_ax, *([None] * (len(shape) - 2)))
    return P(*([None] * len(shape)))


def cache_shardings(cfg, cache, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_pspec(cfg, p, l, mesh)), cache)


# --------------------------------------------------------------------------
# inputs / outputs
# --------------------------------------------------------------------------

def inputs_shardings(cfg, mesh, shape_spec):
    """NamedShardings for the input dict of this (cfg, shape) cell —
    mirrors launch.dryrun.input_specs."""
    B = shape_spec.global_batch
    tok = NamedSharding(mesh, batch_pspec(mesh, B))
    if cfg.embed_input == "tokens":
        inp = tok
    else:
        inp = NamedSharding(mesh, batch_pspec(mesh, B, (None, None)))
    if shape_spec.kind == "train":
        return {"inputs": inp, "labels": tok}
    if shape_spec.kind == "prefill":
        return {"inputs": inp}
    return {"tokens": tok,
            "pos": NamedSharding(mesh, batch_pspec(mesh, B, ()))}


def last_logits_sharding(cfg, mesh, batch: int):
    sizes = _axis_sizes(mesh)
    v_ax = "model" if ("model" in sizes
                       and cfg.vocab_size % sizes["model"] == 0) else None
    return NamedSharding(mesh, batch_pspec(mesh, batch, (v_ax,)))


# --------------------------------------------------------------------------
# symbolic specs (packed-artifact manifests)
# --------------------------------------------------------------------------
# A packed artifact records each leaf's *symbolic* PartitionSpec — axis
# names without sizes — so any later mesh can place the leaf directly
# (ckpt/packed.py). The symbolic mesh below has size-1 axes, which makes
# the divisibility guard in the rules above vacuous: the rule's full
# intent survives into the manifest, and `guard_pspec` re-applies the
# guard against the real mesh at load time.

SYMBOLIC_AXES = ("data", "model")


class _SymbolicMesh:
    def __init__(self, axes):
        self.axis_names = tuple(axes)
        self.devices = np.empty((1,) * len(self.axis_names))


def symbolic_mesh(axes=SYMBOLIC_AXES):
    """Shape-only stand-in whose every axis divides everything — rules
    evaluated against it return the unguarded symbolic spec."""
    return _SymbolicMesh(axes)


def pspec_to_json(spec) -> list:
    """PartitionSpec -> JSON-safe list (entries: None | str | [str])."""
    return [list(a) if isinstance(a, tuple) else a for a in tuple(spec)]


def pspec_from_json(entries) -> P:
    return P(*[tuple(a) if isinstance(a, list) else a for a in entries])


def drop_axes(spec, axes) -> P:
    """Remove the named mesh axes from a spec (replicating those dims):
    serving loads drop "data" by default — weights replicate over the
    data-parallel shards, FSDP-style gathering is a training concern."""
    axes = set(axes)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            left = tuple(a for a in entry if a not in axes)
            return left if len(left) > 1 else (left[0] if left else None)
        return None if entry in axes else entry
    return P(*[keep(a) for a in tuple(spec)])


def guard_pspec(shape, spec, mesh) -> P:
    """Re-apply the divisibility guard of a symbolic spec against a
    real mesh: an axis is dropped (dim replicated) when the mesh lacks
    it or the dim does not divide its size. Short specs are padded with
    None to the leaf's rank."""
    sizes = _axis_sizes(mesh)
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))

    def ok(dim, ax):
        if ax is None:
            return True
        axes = ax if isinstance(ax, tuple) else (ax,)
        if any(a not in sizes for a in axes):
            return False
        return _div(dim, ax, sizes)

    return P(*[a if ok(d, a) else None for d, a in zip(shape, entries)])
