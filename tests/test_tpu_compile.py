"""The main path's Pallas kernels and served steps compile for a TPU v5e.

Each test lowers and compiles for a described `v5e:2x2` topology (the
TPU compiler is installed; no chip is attached), at qwen3-0.6b widths:
what Mosaic refuses here (an unsupported cast, a block shape off the
(8, 128) tiling, VMEM overflow, a kernel GSPMD cannot partition) would
fail the same way on the chip. Nothing runs, so these say nothing about
results or time.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and under pytest-xdist only
the worker that runs this file may take it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.bcq_matmul import bcq_expert_matmul, bcq_gemv, bcq_matmul
from repro.kernels.paged_attention import paged_attention, paged_attention_quant

BITS = 3
KN = [(1024, 3072), (3072, 1024)]          # qwen3-0.6b wg/wu and wd
# paged decode at qwen3-0.6b attention widths
B, HKV, REP, HD, PAGE, N_PAGES, T, KV_BITS = 4, 8, 2, 128, 64, 64, 8, 4
LAYERS = 28


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Compiled HLO text; asserts a Mosaic kernel is in it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("K,N", KN)
@pytest.mark.parametrize("group_size", [0, 128])
@pytest.mark.parametrize("kernel,M", [(bcq_gemv, 8), (bcq_matmul, 256)])
def test_bcq_kernels_compile(one_chip, kernel, M, group_size, K, N):
    G = K // group_size if group_size else 1
    _compile(kernel, _sds(one_chip, (M, K), jnp.bfloat16),
             _sds(one_chip, (BITS, K // 32, N), jnp.uint32),
             _sds(one_chip, (G, N, BITS), jnp.float32),
             _sds(one_chip, (G, N), jnp.float32))


@pytest.mark.parametrize("K,N", KN)
@pytest.mark.parametrize("group_size", [0, 128])
def test_bcq_expert_matmul_compiles(one_chip, group_size, K, N):
    E = 8
    G = K // group_size if group_size else 1
    _compile(bcq_expert_matmul, _sds(one_chip, (E, 16, K), jnp.bfloat16),
             _sds(one_chip, (E, BITS, K // 32, N), jnp.uint32),
             _sds(one_chip, (E, G, N, BITS), jnp.float32),
             _sds(one_chip, (E, G, N), jnp.float32))


def test_paged_attention_compiles(one_chip):
    pages = _sds(one_chip, (LAYERS, N_PAGES, PAGE, HKV, HD), jnp.bfloat16)
    _compile(paged_attention, _sds(one_chip, (B, HKV, REP, HD), jnp.bfloat16),
             pages, pages, _sds(one_chip, (B, T), jnp.int32),
             _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (), jnp.int32))


@pytest.mark.parametrize("kv_group_size", [0, 64])
def test_paged_attention_quant_compiles(one_chip, kv_group_size):
    G = HD // kv_group_size if kv_group_size else 1
    rows = [_sds(one_chip, (LAYERS, N_PAGES, PAGE, width), dt)
            for width, dt in (
        (HKV * KV_BITS * HD // 32, jnp.uint32),
        (HKV * G * KV_BITS, jnp.float32),
        (HKV * G, jnp.float32))]
    _compile(paged_attention_quant,
             _sds(one_chip, (B, HKV, REP, HD), jnp.bfloat16), *rows, *rows,
             _sds(one_chip, (B, T), jnp.int32), _sds(one_chip, (B,), jnp.int32),
             _sds(one_chip, (), jnp.int32))


def _decode_step_text(topo, monkeypatch, mesh_shape, kv_bits):
    """Compiled HLO of the engine's paged decode step for w3/g128
    qwen3-0.6b over a (data, model) mesh of the described chips, weights
    replicated, the page pool placed by the serving rules. The
    program's own dispatch asks jax.default_backend(), which is the CPU
    here, so this turns the Mosaic kernels on."""
    from repro.configs import get_config
    from repro.dist.context import mesh_context
    from repro.dist.sharding import cache_shardings
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.models.model import init_paged_cache
    from repro.quant import QuantSpec
    from repro.quant.abstract import quantize_params_abstract
    from repro.serve import compile_cache

    monkeypatch.setattr(ops, "FORCE_PALLAS", True)
    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = get_config("qwen3-0.6b")
    spec = QuantSpec.from_config(cfg.quant, mode="packed", bits=BITS,
                                 group_size=128)
    params = quantize_params_abstract(cfg, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))), spec=spec)
    n_data, n_model = mesh_shape
    n_pages = B * T + n_data
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, n_pages, PAGE, B, kv_bits=kv_bits))
    mesh = make_mesh(mesh_shape, ("data", "model"),
                     devices=topo.devices[:n_data * n_model])
    replicated = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("data"))
    place = lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
    params = jax.tree.map(lambda x: place(x, replicated), params)
    cache = jax.tree.map(place, cache, cache_shardings(cfg, cache, mesh))
    i32 = lambda shape: _sds(rows, shape, jnp.int32)
    with mesh_context(mesh):
        step = compile_cache.get("decode_paged", cfg, mesh)
        return step.lower(params, cache, i32((B, 1)), i32((B,)),
                          i32((B, T)), i32((B,)), i32((B,))).compile().as_text()


@pytest.mark.parametrize("kv_bits", [0, KV_BITS])
@pytest.mark.parametrize("n_chips", [1, 4])
def test_served_decode_step_compiles(topo, monkeypatch, n_chips, kv_bits):
    """On one chip and over a 4-chip data mesh (kernels under
    shard_map)."""
    text = _decode_step_text(topo, monkeypatch, (n_chips, 1), kv_bits)
    # seven BCQ GEMMs and the paged attention in the layer scan body
    assert text.count("tpu_custom_call") >= 8


@pytest.mark.parametrize("kv_bits", [0, KV_BITS])
def test_served_decode_step_compiles_tensor_parallel(topo, monkeypatch,
                                                     kv_bits):
    """Over a 2x2 data x model mesh: each device expands and multiplies
    only its half of every BCQ weight's columns (d_ff 3072 -> 1536 for
    the gate/up projections), never the whole weight."""
    text = _decode_step_text(topo, monkeypatch, (2, 2), kv_bits)
    assert text.count("tpu_custom_call") >= 8
    kernel_outs = re.findall(r"= \w+\[(\d+),(\d+)\]\S* custom-call\(",
                             text)
    widths = {int(n) for _, n in kernel_outs}
    assert 1536 in widths and 3072 not in widths, sorted(widths)


# the served page pools at their real sizes: (arch, pages, slots, pages
# a row); an extend admission of one 128-token chunk
SERVED = [("qwen3-0.6b", 512, 32, 32), ("qwen3-4b", 640, 64, 16)]
EXTEND_TOKENS = 128
POOL_OP = re.compile(r"= \w+\[([\d,]*)\]\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(")


def _pool_ops(text, pool_shapes):
    """(computation, op, shape) of every copy, dynamic-slice and
    dynamic-update-slice in optimized HLO whose result has one of
    pool_shapes."""
    found, comp = [], None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            comp = "ENTRY" if line.startswith("ENTRY") else "nested"
        m = POOL_OP.search(line)
        if m:
            shape = tuple(int(d) for d in m.group(1).split(",") if d)
            if shape in pool_shapes:
                found.append((comp, m.group(2), shape))
    return found


@pytest.mark.parametrize("kind", ["decode_paged", "extend_paged"])
@pytest.mark.parametrize("kv_bits", [0, KV_BITS])
@pytest.mark.parametrize("arch,n_pages,slots,row_pages", SERVED)
def test_served_steps_update_the_pool_in_place(one_chip, monkeypatch, arch,
                                               n_pages, slots, row_pages,
                                               kv_bits, kind):
    """The decode and extend programs at real pool sizes. Raw bf16
    pages at head_dim 128 ride the layer scan's carry: no copy, slice
    or update has a layer's pool shape or the stacked pool's, and the
    program needs under 1 % of the pool in temporaries (a pool moved by
    value through the scan needed a whole second pool). The coded pool
    of kv_bits 4 has scale rows narrower than a lane tile and goes a
    layer at a time: its stack is never copied inside the scan."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import init_params
    from repro.models.model import init_paged_cache
    from repro.quant import QuantSpec
    from repro.quant.abstract import quantize_params_abstract
    from repro.serve import compile_cache

    monkeypatch.setattr(ops, "FORCE_PALLAS", True)
    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = get_config(arch)
    spec = QuantSpec.from_config(cfg.quant, mode="packed", bits=BITS,
                                 group_size=128)
    on = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    params = on(quantize_params_abstract(cfg, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))), spec=spec))
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, n_pages, PAGE, slots, kv_bits=kv_bits))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    step = compile_cache.get(kind, cfg)
    if kind == "decode_paged":
        args = (i32(slots, 1), i32(slots), i32(slots, row_pages),
                i32(slots), i32(slots))
    else:
        args = (i32(1, EXTEND_TOKENS), i32(1), i32(1, row_pages), i32(1))
    compiled = step.lower(params, on(cache), *args).compile()
    leaves = jax.tree.leaves(cache)
    stacks = {tuple(x.shape) for x in leaves}
    layers = {tuple(x.shape[1:]) for x in leaves} | {
        (1,) + tuple(x.shape[1:]) for x in leaves}
    found = _pool_ops(compiled.as_text(), stacks | layers)
    if kv_bits:
        assert not [f for f in found
                    if f[0] != "ENTRY" and f[1] == "copy" and f[2] in stacks]
        return
    assert not found, sorted(set(found))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.01 * pool_bytes, (temp, pool_bytes)
