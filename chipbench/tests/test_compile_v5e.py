"""Compile each cell's programs at their real sizes for a described
TPU v5e, without the chip: the weight maker, the decode step, the
largest prefill bucket with its page scatter (or the largest extend
shape, for a mix with shared prefixes) and the reference's layer at
its largest length. What the chip's compiler refuses, or what does not
fit one chip's 16 GB beside the page pool, fails here.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_compile_v5e.py
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench.lib import spec
from chipbench.lib.weights import dims, program_params

HBM = 16e9
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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


@pytest.mark.parametrize("cell", CELLS)
def test_cell_compiles_and_fits(chip, cell, monkeypatch):
    from repro.kernels import ops
    from repro.models.model import init_paged_cache
    from repro.serve import compile_cache
    from repro.serve.engine import bucket_len
    from chipbench.lib.traffic import Traffic
    import chipbench.run as run

    monkeypatch.setattr(ops, "FORCE_PALLAS", True)
    monkeypatch.setattr(ops, "interpret", lambda: False)
    bench = spec.load_benchmark()
    c = spec.cell(cell, bench)
    config = spec.load_config(c["config"], bench)
    mix = spec.load_traffic(c["traffic"])
    dm = dims(config)
    cfg = run.program_config(config)
    q, sv = config["quant"], config["serve"]
    page = sv["page_size"]

    maker = jax.jit(lambda: program_params(0, dm, q["bits"],
                                           q["group_size"])).lower()
    params = jax.eval_shape(lambda: program_params(0, dm, q["bits"],
                                                   q["group_size"]))
    made = maker.compile()
    assert _peak(made) < HBM, ("weight maker", _peak(made))

    B, T = mix["slots"], -(-mix["max_len"] // page)
    n_pages = mix.get("pages") or B * T + 1
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, n_pages, page, B,
                                                    kv_bits=sv["kv_bits"]))
    resident = _bytes(params) + _bytes(cache)
    p, kv = _on(chip, params), _on(chip, cache)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    step = compile_cache.get("decode_paged", cfg).lower(
        p, kv, i32(B, 1), i32(B), i32(B, T), i32(B), i32(B)).compile()
    assert "tpu_custom_call" in step.as_text()
    assert _peak(step) < HBM, ("decode", _peak(step) / 1e9)

    traffic = Traffic(mix, dm["vocab"], 0)
    longest = int(traffic.prompt_lengths().max())
    if traffic.prefixes:
        n = len(traffic.prefixes[0])
        C = bucket_len(longest - n, mix["max_len"])
        npg = -(-longest // page)
        ext = compile_cache.get("extend_paged", cfg).lower(
            p, kv, i32(1, C), i32(1), i32(1, npg), i32(1)).compile()
        assert _peak(ext) < HBM, ("extend", _peak(ext) / 1e9)
        S = bucket_len(n, mix["max_len"])
    else:
        S = bucket_len(longest, mix["max_len"])
    pre = compile_cache.get("prefill", cfg).lower(
        p, i32(1, S), i32(1), S).compile()
    rows = jax.eval_shape(lambda a, b: compile_cache.get("prefill", cfg)(
        a, b, jnp.zeros((1,), jnp.int32), S)[1], params,
        jax.ShapeDtypeStruct((1, S), jnp.int32))
    assert resident + _peak(pre) - _bytes(params) < HBM, \
        ("prefill", (resident + _peak(pre)) / 1e9)
    scat = compile_cache.get("scatter_prefill", cfg).lower(
        kv, _on(chip, rows), i32(), i32(-(-S // page)), i32()).compile()
    assert _peak(scat) < HBM
    print(f"{cell}: weights {_bytes(params) / 1e9:.2f} GB, pool "
          f"{_bytes(cache) / 1e9:.2f} GB, decode peak "
          f"{_peak(step) / 1e9:.2f} GB, prefill[{S}] peak "
          f"{_peak(pre) / 1e9:.2f} GB")


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
