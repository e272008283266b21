"""Distribution-layer unit tests on a small host mesh (4 fake devices via
subprocess would be heavy; these validate the RULES, and a 4-device
in-process mesh exercises pjit end-to-end numerically)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, smoke_config
from repro.dist.sharding import (batch_pspec, cache_pspec, param_pspec,
                                 params_shardings)
from repro.launch.mesh import make_production_mesh  # noqa: F401 (import ok)


class FakeMesh:
    """Shape-only stand-in so rule tests don't need 256 devices."""

    def __init__(self, shape, names):
        self.axis_names = names
        import numpy as _np
        self.devices = _np.empty(shape)


MESH = FakeMesh((16, 16), ("data", "model"))


def _spec(cfg, params_path_leaf):
    path, leaf = params_path_leaf
    return param_pspec(cfg, path, leaf, MESH)


def test_param_rules_qwen():
    cfg = get_config("qwen3-4b")
    p = jax.eval_shape(lambda k: __import__("repro.models.model",
                                            fromlist=["init_params"])
                       .init_params(cfg, k), jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_leaves_with_path(p)
    by_name = {}
    for path, leaf in flat:
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        by_name[name] = param_pspec(cfg, path, leaf, MESH)
    assert by_name["wq"] == P(None, "data", "model")
    assert by_name["wo"] == P(None, "model", "data")
    assert by_name["embed"] == P("model", "data")
    assert by_name["ln"] == P(None, None)


def test_expert_rules_ep_vs_tp():
    # qwen3-moe: 128 experts % 16 == 0 -> EP (E on model)
    cfg = get_config("qwen3-moe-235b-a22b")
    leaf = jax.ShapeDtypeStruct((cfg.n_groups, 128, 4096, 1536), jnp.bfloat16)
    path = (jax.tree_util.DictKey("blocks"), jax.tree_util.DictKey("L0"),
            jax.tree_util.DictKey("moe"), jax.tree_util.DictKey("wg"))
    assert param_pspec(cfg, path, leaf, MESH)[1] == "model"
    # mixtral: 8 experts % 16 != 0 -> TP inside experts
    cfg2 = get_config("mixtral-8x7b")
    leaf2 = jax.ShapeDtypeStruct((32, 8, 4096, 14336), jnp.bfloat16)
    spec2 = param_pspec(cfg2, path, leaf2, MESH)
    assert spec2[1] is None and spec2[3] == "model"


def test_divisibility_guard_drops_axis():
    cfg = get_config("minicpm3-4b")
    # vocab 73448 % 16 != 0 -> model axis dropped on embed vocab dim
    leaf = jax.ShapeDtypeStruct((73448, 2560), jnp.bfloat16)
    path = (jax.tree_util.DictKey("embed"),)
    spec = param_pspec(cfg, path, leaf, MESH)
    assert spec[0] is None


def test_kv_cache_seq_sharding_for_batch1():
    cfg = get_config("mixtral-8x7b")
    path = (jax.tree_util.DictKey("L0"), jax.tree_util.DictKey("k"))
    # B=128, kv_heads=8 < model=16: batch on data, SEQUENCE on model
    # (flash-decode partial softmax; EXPERIMENTS.md §Perf H1)
    leaf = jax.ShapeDtypeStruct((32, 128, 8, 4096, 128), jnp.bfloat16)
    s = cache_pspec(cfg, path, leaf, MESH)
    assert s[1] == "data" and s[3] == "model"
    # B=1: sequence over BOTH axes
    leaf1 = jax.ShapeDtypeStruct((32, 1, 8, 4096, 128), jnp.bfloat16)
    s1 = cache_pspec(cfg, path, leaf1, MESH)
    assert s1[1] is None and s1[3] == ("data", "model")
    # divisible kv heads (gemma2 kv=16): heads on model, seq unsharded
    cfg2 = get_config("gemma2-27b")
    leaf2 = jax.ShapeDtypeStruct((23, 128, 16, 4096, 128), jnp.bfloat16)
    s2 = cache_pspec(cfg2, path, leaf2, MESH)
    assert s2[2] == "model" and s2[3] is None


def test_qt_leaves_shard_like_dense():
    from repro.quant.abstract import quantized_leaf_abstract
    cfg = get_config("qwen3-4b")
    qt = quantized_leaf_abstract(
        jax.ShapeDtypeStruct((cfg.n_groups, 2560, 4096), jnp.bfloat16), 3)
    base = (jax.tree_util.DictKey("blocks"), jax.tree_util.DictKey("L0"),
            jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq"))
    flat = jax.tree_util.tree_flatten_with_path(qt)[0]
    specs = {str(p[-1]): param_pspec(cfg, base + p, l, MESH) for p, l in flat}
    assert specs[".codes"] == P(None, None, "data", "model")
    assert specs[".alphas"] == P(None, None, "model", None)
    assert specs[".betas"] == P(None, None, "model")


def test_batch_pspec_fallbacks():
    pod_mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    assert batch_pspec(pod_mesh, 256) == P(("pod", "data"), None)
    assert batch_pspec(pod_mesh, 16) == P("data", None)  # 16 % 32 != 0
    assert batch_pspec(pod_mesh, 1) == P(None, None)


@pytest.mark.slow
def test_four_device_pjit_numeric():
    """End-to-end numeric check under a real (2,2) mesh in a subprocess
    with 4 fake devices: sharded forward == single-device forward."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.models import init_params, forward
from repro.dist.sharding import params_shardings, inputs_shardings
from repro.configs.base import ShapeSpec

cfg = smoke_config("qwen3-0.6b").replace(dtype="float32", d_model=64,
                                         n_heads=4, n_kv_heads=2, head_dim=16)
p = init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
want, _ = forward(cfg, p, toks)
from repro.dist.context import mesh_context
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
with mesh_context(mesh):
    psh = params_shardings(cfg, p, mesh)
    pp = jax.device_put(p, psh)
    f = jax.jit(lambda p_, t_: forward(cfg, p_, t_)[0], in_shardings=(psh, None))
    got = f(pp, toks)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
print("PJIT-NUMERIC-OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                        "PYTHONPATH": "src"},
                       cwd=__import__("pathlib").Path(__file__).parents[1],
                       timeout=300)
    assert "PJIT-NUMERIC-OK" in r.stdout, r.stderr[-2000:]
