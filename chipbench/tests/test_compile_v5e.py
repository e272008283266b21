"""Compile each cell's programs at their real sizes for described TPU
v5e chips, without them: the weight maker, the decode step, the
largest prefill bucket with its page scatter (or the largest extend
shape, for a mix with shared prefixes) and the reference's layer at
its largest length. A cell on several chips compiles over a mesh of
that many of a described v5e:2x2's devices, shaped as its
configuration's serve.mesh states, each leaf placed as the run places
it. What the chip's compiler refuses, or what does not fit a chip's
16 GB beside its share of the page pool, fails here.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_compile_v5e.py
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from chipbench.lib import spec
from chipbench.lib.weights import dims

HBM = 16e9
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
# a test-only cell: Qwen3-4B's decode mix with each layer split over
# four chips (KV heads 2 a chip), as a configuration stating
# serve.mesh {"data": 1, "model": 4} would run it
MESH4 = {"name": "qwen3-4b.decode.model4", "config": "qwen3-4b.w3g128",
         "traffic": "decode", "chips": 4, "why": "test only"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _on(chip, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=chip), tree)


def _bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _peak(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


class Placement:
    """Where a cell's arrays live: one described chip, or a mesh over
    the first `chips` of them (as chipbench/run.py:serve_mesh makes it
    over real ones) with the serving rules' shardings."""

    def __init__(self, topo, config, chips):
        self.mesh = None
        self.one = SingleDeviceSharding(topo.devices[0])
        if chips > 1:
            from repro.launch.mesh import make_mesh
            m = config["serve"]["mesh"]
            self.mesh = make_mesh((m["data"], m["model"]),
                                  ("data", "model"),
                                  devices=topo.devices[:chips])

    def params(self, cfg, tree):
        if self.mesh is None:
            return _on(self.one, tree)
        from repro.dist.sharding import params_shardings
        return _placed(tree, params_shardings(cfg, tree, self.mesh))

    def cache(self, cfg, tree):
        if self.mesh is None:
            return _on(self.one, tree)
        from repro.dist.sharding import cache_shardings
        return _placed(tree, cache_shardings(cfg, tree, self.mesh))

    def i32(self, *shape):
        s = self.one if self.mesh is None else NamedSharding(
            self.mesh, PartitionSpec())
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=s)

    def context(self):
        from contextlib import nullcontext
        from repro.dist.context import mesh_context
        return nullcontext() if self.mesh is None else \
            mesh_context(self.mesh)


def _placed(tree, shardings):
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, shardings)


def _cell(topo, bench, cell, monkeypatch, *, mesh=None):
    """(config, mix, program config, placement, params, cache shapes) of
    a cell, with the Mosaic kernels on; `mesh` overrides the
    configuration's serve.mesh."""
    from repro.kernels import ops
    from repro.models.model import init_paged_cache
    monkeypatch.setattr(ops, "FORCE_PALLAS", True)
    monkeypatch.setattr(ops, "interpret", lambda: False)
    c = spec.cell(cell, bench)
    config = spec.load_config(c["config"], bench)
    if mesh is not None:
        config["serve"]["mesh"] = mesh
    mix = spec.load_traffic(c["traffic"])
    prog = spec.program_module(config)
    cfg = prog.model_config(config)
    sv = config["serve"]
    place = Placement(topo, config, c["chips"])
    B, T = mix["slots"], -(-mix["max_len"] // sv["page_size"])
    n_pages = mix.get("pages") or B * T + 1
    params = jax.eval_shape(lambda: prog.params(0, config, None))
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, n_pages, sv["page_size"], B, kv_bits=sv["kv_bits"]))
    return config, mix, cfg, place, prog, params, cache


def _weight_maker(place, prog, config):
    """The program's weight maker compiled where the run makes them: on
    the chip, or on the mesh in the leaves' serving shardings."""
    if place.mesh is None:
        return jax.jit(lambda: prog.params(0, config, None),
                       out_shardings=place.one).lower().compile()
    return jax.jit(lambda: prog.params(0, config, place.mesh)).lower() \
        .compile()


def _device_bytes(tree):
    """Bytes of placed shapes on one device: its shard of each leaf."""
    return sum(np.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def _compiles_and_fits(topo, bench, cell, monkeypatch, *, mesh=None):
    """Compile a cell's programs where it runs them and check that each
    fits a chip beside what stays resident there; returns the compiled
    weight maker and decode step and the placed params and pool."""
    from repro.serve import compile_cache
    from repro.serve.engine import bucket_len
    from chipbench.lib.traffic import Traffic

    config, mix, cfg, place, prog, params, cache = _cell(
        topo, bench, cell, monkeypatch, mesh=mesh)
    page = config["serve"]["page_size"]
    dm = dims(config)

    made = _weight_maker(place, prog, config)
    assert _peak(made) < HBM, ("weight maker", _peak(made) / 1e9)

    B, T = mix["slots"], -(-mix["max_len"] // page)
    p, kv = place.params(cfg, params), place.cache(cfg, cache)
    i32 = place.i32
    with place.context():
        step = compile_cache.get("decode_paged", cfg, place.mesh).lower(
            p, kv, i32(B, 1), i32(B), i32(B, T), i32(B), i32(B)).compile()
    assert "tpu_custom_call" in step.as_text()
    assert _peak(step) < HBM, ("decode", _peak(step) / 1e9)

    traffic = Traffic(mix, dm["vocab"], 0)
    longest = int(traffic.prompt_lengths().max())
    with place.context():
        if traffic.prefixes:
            n = len(traffic.prefixes[0])
            C = bucket_len(longest - n, mix["max_len"])
            npg = -(-longest // page)
            ext = compile_cache.get("extend_paged", cfg, place.mesh).lower(
                p, kv, i32(1, C), i32(1), i32(1, npg), i32(1)).compile()
            assert _peak(ext) < HBM, ("extend", _peak(ext) / 1e9)
            S = bucket_len(n, mix["max_len"])
        else:
            S = bucket_len(longest, mix["max_len"])
        prefill = compile_cache.get("prefill", cfg, place.mesh)
        pre = prefill.lower(p, i32(1, S), i32(1), S).compile()
        rows = jax.eval_shape(lambda a, b: prefill(
            a, b, jnp.zeros((1,), jnp.int32), S)[1], params,
            jax.ShapeDtypeStruct((1, S), jnp.int32))
        # the prefill's peak counts the weights; the pool stays beside
        assert _device_bytes(kv) + _peak(pre) < HBM, \
            ("prefill", (_device_bytes(kv) + _peak(pre)) / 1e9)
        scat = compile_cache.get("scatter_prefill", cfg, place.mesh).lower(
            kv, place.cache(cfg, rows), i32(), i32(-(-S // page)),
            i32()).compile()
    assert _peak(scat) < HBM
    print(f"{cell}: weights {_bytes(params) / 1e9:.2f} GB, pool "
          f"{_bytes(cache) / 1e9:.2f} GB; a chip's weights "
          f"{_device_bytes(p) / 1e9:.2f} GB, pool "
          f"{_device_bytes(kv) / 1e9:.2f} GB; decode peak "
          f"{_peak(step) / 1e9:.2f} GB, prefill[{S}] peak "
          f"{_peak(pre) / 1e9:.2f} GB, weight maker peak "
          f"{_peak(made) / 1e9:.2f} GB")
    return made, step, p, kv


@pytest.mark.parametrize("cell", CELLS)
def test_cell_compiles_and_fits(topo, cell, monkeypatch):
    _compiles_and_fits(topo, spec.load_benchmark(), cell, monkeypatch)


def test_four_chip_cell_makes_its_weights_and_decodes_in_shards(
        topo, monkeypatch):
    """Qwen3-4B's programs for a test-only 4-chip cell on a (data=1,
    model=4) mesh, the weights made through the program's own
    params(seed, config, mesh): each chip makes and holds about a
    quarter of the weights and of the page pool, and fits its 16 GB."""
    bench = dict(spec.load_benchmark())
    bench["workloads"] = bench["workloads"] + [MESH4]
    made, step, p, kv = _compiles_and_fits(
        topo, bench, MESH4["name"], monkeypatch,
        mesh={"data": 1, "model": 4})
    whole = _bytes(p) + _bytes(kv)
    # the maker's outputs are this chip's shards, not the whole model;
    # the replicated norms and per-group scales of the row-split
    # projections come on top of a quarter
    assert made.memory_analysis().output_size_in_bytes < 0.35 * _bytes(p)
    assert _device_bytes(p) + _device_bytes(kv) < 0.3 * whole
    assert step.memory_analysis().argument_size_in_bytes < 0.3 * whole


@pytest.mark.parametrize("cell", CELLS)
def test_reference_layer_compiles(chip, cell):
    """The reference's layer and head at the longest length the cell's
    check can score, with the program's state already freed."""
    ref_mod = None
    bench = spec.load_benchmark()
    c = spec.cell(cell, bench)
    config = spec.load_config(c["config"], bench)
    mix = spec.load_traffic(c["traffic"])
    ref_mod = spec.reference_module(config)
    dm = dims(config)
    dmt = tuple(sorted(dm.items()))
    n = mix["prompt"]["max"] + mix["output"]["max"]
    if "prefixes" in mix:
        n += mix["prefixes"]["tokens"]
    S = -(-n // ref_mod.LEN_UNIT) * ref_mod.LEN_UNIT
    R = ref_mod.ROWS_PER_BLOCK
    x = jax.ShapeDtypeStruct((R, S, dm["d"]), jnp.float32, sharding=chip)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    q = config["quant"]
    with jax.default_matmul_precision("highest"):
        for control in (False, True):
            lay = ref_mod._layer.lower(key, i, x, x, dmt, q["bits"],
                                       q["group_size"], control).compile()
            assert _peak(lay) < HBM, ("reference layer", _peak(lay) / 1e9)
            nxt = jax.ShapeDtypeStruct((R, S), jnp.int32, sharding=chip)
            head = ref_mod._head.lower(key, x, x, nxt, dmt,
                                       control).compile()
            assert _peak(head) < HBM, ("reference head", _peak(head) / 1e9)
