"""The program side of a configuration (chipbench/programs/) and a cell
on several chips, on the CPU: the Qwen3 weights are the same bits they
were before they moved there, and a run on a (data=1, model=4) mesh of
four virtual devices spreads them over the four, reads the fullest
device's memory and comes out correct.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_programs.py
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench.lib import spec
from chipbench.lib.spec import ROOT
from chipbench.tests import tiny

# sha256 of every leaf (key path, dtype, shape, bytes) of the tiny
# config's served parameters, taken from lib/weights.py:program_params
# before it moved to programs/qwen3.py:params
PARAMS_SHA256 = {
    5: "b0bc06877c870943aee0e23e52b9408f797a2d1eafd467a45459f53b37b1b9a6",
    2 ** 33 + 77:
        "618190854138d5f4741b7380b1533229a5ee3d33889f88336219e84eb5672cc9",
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PARAMS_SHA256))
def test_qwen3_params_are_the_same_bits_as_before_the_move(seed):
    config = tiny.config()
    params = spec.program_module(config).params(seed, config, None)
    assert digest(params) == PARAMS_SHA256[seed]


def test_qwen3_model_config_is_checked_against_the_file():
    config = tiny.config()
    prog = spec.program_module(config)
    assert prog.model_config(config).n_kv_heads == 2
    config["num_key_value_heads"] = 4
    with pytest.raises(ValueError):
        prog.model_config(config)


def test_a_cell_on_several_chips_takes_the_mesh_its_file_states():
    import chipbench.run as run
    config = tiny.config()
    assert run.serve_mesh(config, 1) is None
    with pytest.raises(ValueError):         # no serve.mesh
        run.serve_mesh(config, 4)
    config["serve"]["mesh"] = {"data": 2, "model": 1}
    with pytest.raises(ValueError):         # 2 chips, not 4
        run.serve_mesh(config, 4)


MESH_CELL = r"""
import json
import jax
import numpy as np
import chipbench.run as run
from chipbench.lib import check, spec
from chipbench.tests import tiny

run.enable_cache = lambda: None
peaks = {}


def live_bytes(dev):
    # what the CPU can say of one device's memory: its live arrays
    n = sum(s.data.nbytes for a in jax.live_arrays()
            for s in a.addressable_shards if s.device == dev)
    peaks[dev.id] = n
    return n


run.device_peak = live_bytes
config = tiny.config(heads=8, kv_heads=4)
config["serve"]["mesh"] = {"data": 1, "model": 4}
seed = 2 ** 33 + 5
whole = jax.tree.leaves(spec.program_module(config).params(seed, config,
                                                           None))
placed = {}


def hook(eng):
    leaves = jax.tree.leaves(eng.params)
    placed["same_values"] = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves, whole))
    placed["devices"] = sorted({d.id for x in leaves
                                for d in x.sharding.device_set})
    per = {}
    for x in leaves:
        for s in x.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    placed["bytes_per_device"] = per
    placed["bytes"] = sum(x.nbytes for x in leaves)


bench = dict(spec.load_benchmark(), workloads=[
    {"name": "tiny.mesh4", "config": "tiny", "traffic": "tiny", "chips": 4,
     "why": "test only"}])
r = run.run_cell("tiny.mesh4", seed, 1.5, False, bench=bench, config=config,
                 mix=tiny.mix("open", prefixes=True),
                 limits={check.GAP_KEY: 0.05}, engine_hook=hook)
print(json.dumps({"result": r, "peaks": peaks, "placed": placed}))
"""


def test_cell_on_four_devices_shards_the_model_and_reads_the_fullest():
    """run_cell for a test-only `chips: 4` cell whose configuration
    states a (data=1, model=4) mesh, in a process of its own with four
    virtual CPU devices (the count is fixed when JAX starts)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
    p = subprocess.run([sys.executable, "-c", MESH_CELL], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    r, placed = out["result"], out["placed"]
    assert r["correct"], r["checks"]
    assert r["checks"]["scored_tokens"]["value"] > 0
    # every leaf on the mesh's four devices, with the values one device
    # gets; each device holds a quarter of the sharded weights and its
    # copy of the replicated norms and scales, far from the whole
    assert placed["devices"] == [0, 1, 2, 3]
    assert placed["same_values"]
    per = placed["bytes_per_device"]
    assert set(per) == {"0", "1", "2", "3"}
    assert max(per.values()) < 0.5 * placed["bytes"], (per, placed["bytes"])
    # the peak is the fullest of the cell's four devices
    assert set(out["peaks"]) == {"0", "1", "2", "3"}
    assert r["device"]["memory_peak_bytes"] == max(out["peaks"].values())
