"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases (quantize, serve with raw and 4-bit pages, compare logits with
the float32 reference; serve over the 4-device meshes) pass at a small
width with the Pallas kernels in interpret mode."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def small_model(smoke):
    """qwen3-0.6b's block at a width the CPU interpreter serves quickly,
    quantized as the script does: head_dim stays 128, every K a multiple
    of the 128 group size, and every projection's N splits into whole
    128-lane halves on a model axis of 2. -> (cfg, qparams, prompts)"""
    from benchmarks.common import calib_batches_for
    from repro.configs import get_config
    cfg = get_config("qwen3-0.6b").replace(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
        vocab_size=512)
    params = smoke.build_model(cfg, seed=0)
    qparams = smoke.quantize(cfg, params, calib_batches_for("wiki")[:1])
    return cfg, qparams, smoke.make_prompts(seed=0)


def test_phases_at_small_width(smoke, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "FORCE_PALLAS", True)
    smoke.one_chip(*small_model(smoke))


MESH_PHASE = """
import importlib.util
def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
smoke = load("chip_smoke", "chip_smoke.py")
from repro.kernels import ops
ops.FORCE_PALLAS = True
smoke.on_mesh(*load("t", "tests/test_chip_smoke.py").small_model(smoke))
print("MESH-PHASE-OK")
"""


def test_mesh_phase_at_small_width():
    """The --chips 4 phase on four virtual CPU devices, in a process of
    its own (the device count is fixed when JAX starts): both meshes
    match one device and the float32 reference."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", MESH_PHASE],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert "MESH-PHASE-OK" in r.stdout, (r.stdout[-3000:], r.stderr[-3000:])
    assert "mesh data=2 model=2 vs one device: logits within" in r.stdout
