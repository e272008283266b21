"""Kernel dispatch: the one place that decides kernel versus reference.

`bcq_apply(x, qt)` is what `layers.linear` calls for QuantizedTensor
weights, and `paged_decode(...)` is what paged attention decode calls.
Each picks the Pallas kernel on TPU (or when FORCE_PALLAS is set,
running interpret=True off-TPU for tests) and the pure-jnp reference
otherwise. On TPU a shape no kernel takes raises instead of silently
dequantizing the whole weight. Group-wise scales (G > 1) ride the
kernel whenever the packed layout lines up (group_size a multiple of
the 32-bit pack word, so the zero-padded K tail never crosses into a
phantom group). A single-axis expert stack (codes (E, bits, K/32, N))
with a matching batched activation (E, C, k_in) rides the
batched-expert kernel — one launch for the whole MoE layer; deeper
leading dims and ragged groupings fall back to the reference path
off-TPU.

Mosaic kernels cannot be partitioned by GSPMD, so inside a
`dist.context.mesh_context` of more than one device every kernel call
runs under `shard_map`. Activation rows (and page-pool blocks) split
over the data axis as the engine lays them out. The model axis splits
the work, never duplicates it where it divides: a BCQ weight is
column-parallel (each device expands and multiplies its N / model
columns; an expert stack splits its experts instead), and paged decode
splits kv heads. A weight stored with another layout (the contract
projections keep K on the model axis, dist/sharding.py) is resharded
to N at the call. Only what does not divide runs whole on each device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.hw import LANE
from repro.kernels import ref
from repro.kernels.bcq_matmul import bcq_expert_matmul, bcq_gemv, bcq_matmul
from repro.kernels.paged_attention import paged_attention, paged_attention_quant
from repro.quant.packing import WORD

# None = auto: the Pallas kernels run iff the backend is TPU. Tests set
# True to drive them in interpret mode off-TPU, False to force the jnp
# reference. The one switch for every kernel call site.
FORCE_PALLAS: bool | None = None


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    if FORCE_PALLAS is not None:
        return FORCE_PALLAS
    return on_tpu()


def interpret() -> bool:
    """Interpret mode for a Pallas call: only off-TPU, never on the chip."""
    return not on_tpu()


def _mesh():
    """The active multi-device mesh, or None."""
    from repro.dist.context import current_mesh
    mesh = current_mesh()
    return mesh if mesh is not None and mesh.devices.size > 1 else None


def _axis(mesh, name, n, unit=1):
    """`name` if the mesh has that axis and it cuts n into shards that
    are multiples of `unit`, else None."""
    size = dict(mesh.shape).get(name)
    return name if size and n % (size * unit) == 0 else None


def _kernel_groups_ok(qt) -> bool:
    """G > 1 runs the fused kernel iff groups tile the packed K axis:
    group_size divides k_in (validated at construction) AND is a
    multiple of the 32-bit pack word, which together mean k_in is
    already word-aligned (no pad rows outside the last group)."""
    G = qt.alphas.shape[-3]
    if G == 1:
        return True
    return qt.k_in % G == 0 and (qt.k_in // G) % WORD == 0


def _active_codes(qt):
    """Code planes the tensor's scales actually weight. Draft views keep
    the full stored planes (they alias the target's packed words) but
    carry fewer alphas; the slice happens here, at trace time, so the
    smaller plane stack never persists in HBM."""
    if qt.bits == qt.stored_bits:
        return qt.codes
    return qt.codes[..., : qt.bits, :, :]


def _reference(x, qt):
    """Dequantize-then-einsum: the jnp oracle as an execution path."""
    w = _dequant_nd(qt, x.dtype)
    if w.ndim == 3 and x.ndim == 3 and x.shape[0] == w.shape[0]:
        # batched expert matmul: (E, C, k) @ (E, k, n) -> (E, C, n)
        return jnp.einsum("eck,ekn->ecn", x, w)
    return jnp.einsum("...k,...kn->...n", x, w)


def _no_kernel(x, qt, why):
    """A shape the kernels cannot take. Off-TPU it runs the reference;
    on the chip a full dequantize would hide the missing kernel behind
    a many-times larger weight read, so it raises."""
    if on_tpu():
        raise NotImplementedError(
            f"no BCQ kernel for x {tuple(x.shape)} @ weight "
            f"{tuple(qt.shape)} (group_size={qt.group_size}): {why}")
    return _reference(x, qt)


def _gemm(x, codes, alphas, betas):
    """(M, K) rows through the decode- or prefill-shaped kernel."""
    fn = bcq_gemv if x.shape[0] <= 8 else bcq_matmul
    return fn(x, codes, alphas, betas, interpret=interpret())


def _expert_gemm(x, codes, alphas, betas):
    return bcq_expert_matmul(x, codes, alphas, betas, interpret=interpret())


def bcq_apply(x, qt):
    """x (..., k_in) @ QuantizedTensor -> (..., n_out)."""
    codes = _active_codes(qt)
    lead = codes.shape[:-3]
    if not use_pallas():
        return _reference(x, qt)
    if not _kernel_groups_ok(qt):
        return _no_kernel(x, qt, "group_size is not a multiple of "
                          f"{WORD}")
    kp = codes.shape[-2] * WORD
    mesh = _mesh()
    if lead:                      # expert stacks
        if not (len(lead) == 1 and x.ndim == 3 and x.shape[0] == lead[0]):
            return _no_kernel(x, qt, "the expert kernel takes one stack "
                              "axis matching x's leading dim")
        xm = x
        if kp != qt.k_in:
            xm = jnp.pad(xm, ((0, 0), (0, 0), (0, kp - qt.k_in)))
        args = (xm, codes, qt.alphas, qt.betas)
        if mesh is None:
            return _expert_gemm(*args)
        # experts over the model axis (the stored expert-parallel
        # layout), else each expert's columns
        ex = _axis(mesh, "model", lead[0])
        cols = None if ex else _axis(mesh, "model", qt.n_out, LANE)
        return jax.shard_map(
            _expert_gemm, mesh=mesh,
            in_specs=(P(ex), P(ex, None, None, cols), P(ex, None, cols),
                      P(ex, None, cols)),
            out_specs=P(ex, None, cols), check_vma=False)(*args)
    xm = x.reshape(-1, qt.k_in)
    if kp != qt.k_in:
        xm = jnp.pad(xm, ((0, 0), (0, kp - qt.k_in)))
    args = (xm, codes, qt.alphas, qt.betas)
    if mesh is None:
        y = _gemm(*args)
    else:
        rows = _axis(mesh, "data", xm.shape[0])
        cols = _axis(mesh, "model", qt.n_out, LANE)
        # codes (bits, K/32, N), alphas (G, N, bits), betas (G, N)
        y = jax.shard_map(
            _gemm, mesh=mesh,
            in_specs=(P(rows), P(None, None, cols), P(None, cols),
                      P(None, cols)),
            out_specs=P(rows, cols), check_vma=False)(*args)
    return y.reshape(*x.shape[:-1], qt.n_out)


def _dequant_nd(qt, dtype):
    """Dequantize with arbitrary leading dims (expert/group stacks)."""
    acodes = _active_codes(qt)
    lead = acodes.shape[:-3]
    codes = acodes.reshape(-1, *acodes.shape[-3:])
    alphas = qt.alphas.reshape(-1, *qt.alphas.shape[-3:])
    betas = qt.betas.reshape(-1, *qt.betas.shape[-2:])
    ws = jax.vmap(lambda c, a, b: ref.dequant_ref(c, a, b, qt.k_in, dtype))(
        codes, alphas, betas)
    return ws.reshape(*lead, qt.k_in, qt.n_out)


# --------------------------------------------------------------------------
# paged attention decode
# --------------------------------------------------------------------------

_RAW_POOL = ("k_pages", "v_pages")
_CODED_POOL = ("k_codes", "k_alphas", "k_betas",
               "v_codes", "v_alphas", "v_betas")


def paged_decode(q, pool, block_tables, ctx_lens, *, window=None, cap=None):
    """Single-token attention over a layer's page pool: q (B, Hkv, rep,
    hd); pool the raw {"k_pages", "v_pages"} or the binary-coded
    {"k_codes", ...} leaves (models/attention.py:init_paged_kv);
    block_tables (B, T) global page ids; ctx_lens (B,). Returns
    (B, Hkv, rep, hd)."""
    coded = "k_codes" in pool
    leaves = [pool[n] for n in (_CODED_POOL if coded else _RAW_POOL)]
    if not use_pallas():
        fn = ref.paged_attention_quant_ref if coded else \
            ref.paged_attention_ref
        return fn(q, *leaves, block_tables, ctx_lens, window=window,
                  cap=cap)
    kernel = paged_attention_quant if coded else paged_attention

    def run(q, block_tables, ctx_lens, *leaves):
        return kernel(q, *leaves, block_tables, ctx_lens, window=window,
                      cap=cap, interpret=interpret())

    mesh = _mesh()
    if mesh is None:
        return run(q, block_tables, ctx_lens, *leaves)
    # the engine's page pool is split into one contiguous block of pages
    # per data shard, serving the batch rows of that shard; block tables
    # hold global ids, so each shard rebases them onto its block. The
    # kernel fetches a page for every entry, masked or not: the
    # allocator keeps every entry of a row, unused ones included, inside
    # the row's block (serve/kv_cache.py), so no rebased id is negative
    B, Hkv = q.shape[:2]
    n_pages = leaves[0].shape[0]
    data = _axis(mesh, "data", B)
    if data is not None and n_pages % dict(mesh.shape)["data"]:
        data = None
    heads = _axis(mesh, "model", Hkv)
    block = n_pages // dict(mesh.shape)["data"] if data else 0

    def local(q, block_tables, ctx_lens, *leaves):
        if data is not None:
            block_tables = block_tables - jax.lax.axis_index(data) * block
        return run(q, block_tables, ctx_lens, *leaves)

    page_spec = [P(data, None, heads) if coded else
                 P(data, None, heads, None)] * len(leaves)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(data, heads, None, None), P(data, None), P(data),
                  *page_spec),
        out_specs=P(data, heads, None, None),
        check_vma=False)(q, block_tables, ctx_lens, *leaves)
