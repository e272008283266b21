"""CPU tests of the benchmark's yardstick: the trace reduction, the
roofline counts, the traffic generator, the reference, and the refusal
to run without a chip.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import roofline, trace
from chipbench.lib.spec import BENCH_DIR, ROOT
from chipbench.lib.traffic import Traffic, quantile_sizes
from chipbench.tests import tiny

# as a TPU v5e trace names the ops: the whole instruction, layout
# constraints (which repeat the operand types) included
BCQ_TEXT = ("%bcq_matmul.68 = bf16[64,9728]{1,0:T(8,128)(2,1)S(1)} "
            "custom-call(bf16[64,2560]{1,0:T(8,128)(2,1)S(1)} %a, "
            "u32[3,80,9728]{2,1,0:T(8,128)S(1)} %b, f32[20,3,9728]{2,1,0} %c, "
            "f32[20,1,9728]{2,1,0} %d), custom_call_target=\"tpu_custom_call\""
            ", operand_layout_constraints={bf16[64,2560]{1,0}, "
            "u32[3,80,9728]{2,1,0}, f32[20,3,9728]{2,1,0}, "
            "f32[20,1,9728]{2,1,0}}, frontend_attributes={kernel_metadata={}}")
PA_TEXT = ("%paged_attention.9 = bf16[64,8,4,128]{3,2,1,0} custom-call("
           "s32[64,16]{1,0} %bt, s32[64]{0} %cl, bf16[64,8,4,128]{3,2,1,0} %q, "
           "bf16[640,64,8,128]{3,2,1,0} %k, bf16[640,64,8,128]{3,2,1,0} %v), "
           "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
           "{s32[64,16]{1,0}, s32[64]{0}, bf16[64,8,4,128]{3,2,1,0}, "
           "bf16[640,64,8,128]{3,2,1,0}, bf16[640,64,8,128]{3,2,1,0}}")

# bcq_expert_matmul as its Pallas call sees it: x (E, M, K), codes
# (E, bits, K/32, N), alphas (E, G, bits, N), betas (E, G, 1, N)
EXPERT_TEXT = ("%bcq_expert_matmul.3 = bf16[8,64,14336]{2,1,0} custom-call("
               "bf16[8,64,4096]{2,1,0} %x, u32[8,3,128,14336]{3,2,1,0} %c, "
               "f32[8,32,3,14336]{3,2,1,0} %a, f32[8,32,1,14336]{3,2,1,0} "
               "%b), custom_call_target=\"tpu_custom_call\", "
               "operand_layout_constraints={bf16[8,64,4096]{2,1,0}, "
               "u32[8,3,128,14336]{3,2,1,0}, f32[8,32,3,14336]{3,2,1,0}, "
               "f32[8,32,1,14336]{3,2,1,0}}")


# ---------------------------------------------------------------- trace

def _events():
    """A recorded-trace-shaped event set: window [0, 100] ns; ops
    [10,30] bcq (its text in the name, as on a v5e), [20,40] paged
    attention (overlapping; its text in a stat), both inside a while
    [10,40], [60,70] a fusion; host phases: decode_tick [5,45], admit
    [45,80], arrival_wait [80,100]."""
    dev = [("%while.5 = (s32[]) while((s32[]) %t), body=%b", 10.0, 40.0,
            {}),
           (BCQ_TEXT, 10.0, 30.0, {}),
           ("custom-call.7", 20.0, 40.0, {"long_name": PA_TEXT}),
           ("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %x)", 60.0, 70.0,
            {}),
           ("fusion.13", 120.0, 130.0, {})]          # outside the window
    host = [("trace_window", 0.0, 100.0), ("decode_tick", 5.0, 45.0),
            ("admit", 45.0, 80.0), ("arrival_wait", 80.0, 100.0)]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_union_counts_overlap_once_and_returns_gaps():
    busy, gaps = trace.union([(10, 30), (20, 40), (60, 70)], 0, 100)
    assert busy == 40
    assert gaps == [(0, 10), (40, 60), (70, 100)]
    busy, gaps = trace.union([(-5, 5), (95, 120)], 0, 100)
    assert busy == 10 and gaps == [(5, 95)]


def test_reduce_attributes_kernels_and_idle_gaps():
    r = trace.reduce_events(_events())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["kernel_s"] == {"bcq": pytest.approx(20e-9),
                             "paged_attention": pytest.approx(20e-9)}
    gaps = dict(r["breakdown"]["idle_gaps"])
    # idle [0,10], [40,60], [70,100], split over the open host phases
    assert gaps == {"admit": pytest.approx(25e-9),
                    "arrival_wait": pytest.approx(20e-9),
                    "decode_tick": pytest.approx(10e-9),
                    "none": pytest.approx(5e-9)}
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == {"bcq": pytest.approx(20e-9),
                   "paged_attention": pytest.approx(20e-9),
                   "fusion": pytest.approx(10e-9)}


def test_reduce_reads_only_the_planes_of_the_used_devices():
    """A 1-chip run on a 4-chip host: the profiler writes a plane for
    every chip, and the three idle ones change nothing."""
    one = trace.reduce_events(_events())
    tr = _events()
    for i in (1, 2, 3):
        tr["device"][f"/device:TPU:{i}"] = []
    four = trace.reduce_events(tr, devices=[0])
    for k in ("window_s", "busy_s", "kernel_s", "breakdown"):
        assert four[k] == one[k], k
    # over all four planes the same events read a quarter of the busy
    # time: what the device filter is for
    assert trace.reduce_events(tr)["busy_s"] == pytest.approx(
        one["busy_s"] / 4)
    with pytest.raises(ValueError):
        trace.reduce_events(tr, devices=[7])


def test_kernel_of_tells_bcq_from_paged_attention():
    assert trace.kernel_of(BCQ_TEXT) == "bcq"
    assert trace.kernel_of(PA_TEXT) == "paged_attention"
    assert trace.kernel_of(EXPERT_TEXT) == "bcq_expert"
    assert trace.kernel_of("%fusion.1 = bf16[8]{0} fusion(bf16[8] %x)") \
        is None
    # 4-D u32 codes whose expert count or K does not match x's are no
    # batched-expert GEMM
    assert trace.kernel_of(EXPERT_TEXT.replace("bf16[8,64,4096]",
                                               "bf16[4,64,4096]")) is None
    assert trace.kernel_of(EXPERT_TEXT.replace("bf16[8,64,4096]",
                                               "bf16[8,64,2048]")) is None


# ------------------------------------------------------------- roofline

def test_bcq_cost_hand_worked():
    text = trace.op_text((BCQ_TEXT, 0, 0, {}))
    f, b, d = roofline.bcq_cost(roofline.parse_shapes(text))
    # x with a leading batch dim counts all its rows
    f3, b3, d3 = roofline.bcq_cost(roofline.parse_shapes(
        text.replace("bf16[64,2560]", "bf16[2,32,2560]")))
    assert (f3, b3, d3) == (f, b, d)
    assert d == {"M": 64, "K": 2560, "N": 9728, "bits": 3}
    assert f == 2 * 64 * 2560 * 9728
    # out + x (bf16), codes (u32), alphas + betas (f32)
    assert b == (64 * 9728 * 2 + 64 * 2560 * 2 + 3 * 80 * 9728 * 4
                 + 20 * 3 * 9728 * 4 + 20 * 9728 * 4)


def test_bcq_expert_cost_hand_worked():
    text = trace.op_text((EXPERT_TEXT, 0, 0, {}))
    sh = roofline.parse_shapes(text)
    assert roofline.codes_index(sh) is None
    # 8 experts with 64 rows of capacity each; 24 (token, expert) pairs
    # routed here: the work is theirs, not the 512 padded rows'
    f, b, d = roofline.bcq_expert_cost(sh, 24)
    assert d == {"E": 8, "M": 64, "K": 4096, "N": 14336, "bits": 3,
                 "rows": 24}
    assert f == 2 * 24 * 4096 * 14336
    # out + x (bf16), codes (u32), alphas + betas (f32), each once
    assert b == (8 * 64 * 14336 * 2 + 8 * 64 * 4096 * 2
                 + 8 * 3 * 128 * 14336 * 4 + 8 * 32 * 3 * 14336 * 4
                 + 8 * 32 * 14336 * 4)


def test_paged_attention_cost_hand_worked():
    # 2 live rows with 100 and 300 context tokens, 32 q heads, 8 kv
    f, b = roofline.paged_attn_cost(2, 400, 32, 8, 128, 2)
    assert f == 4 * 400 * 32 * 128
    assert b == 2 * 2 * 32 * 128 * 2 + 2 * 400 * 8 * 128 * 2


def test_min_time_takes_the_binding_bound():
    pk = roofline.peaks("TPU v5 lite")
    assert roofline.min_time(197e12, 0, pk) == pytest.approx(1.0)
    assert roofline.min_time(0, 819e9, pk) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_model_flops_hand_worked():
    dm = {"d": 4, "f": 8, "hd": 2, "heads": 2, "kv_heads": 1,
          "layers": 3, "vocab": 10}
    lin = 3 * (4 * (2 + 2) * 2 + 2 * 2 * 4 + 3 * 4 * 8)
    assert roofline.linear_params(dm) == lin
    att = 4 * 2 * 2 * 3
    # one admission of 3 new tokens after 2 shared; one tick, 2 rows
    # with 5 context tokens in all
    got = roofline.model_flops(dm, [(0, 0, 5, 2)], [(0, 0, 2, 5)])
    want = (3 * 2 * lin + 2 * 4 * 10 + att * (3 + 4 + 5)
            + 2 * (2 * lin + 2 * 4 * 10) + att * 5)
    assert got == want


def _file_dims(name):
    from chipbench.lib.weights import dims
    return dims(json.loads((BENCH_DIR / "configs" / name).read_text()))


def test_linear_params_counts_routed_experts_only():
    """Mixtral-8x7B (its config.json: no head_dim, 8 local experts, 2 a
    token, expert width 14336) through the router and two experts."""
    from chipbench.lib.weights import dims
    mixtral = dims({"hidden_size": 4096, "intermediate_size": 14336,
                    "num_hidden_layers": 32, "num_attention_heads": 32,
                    "num_key_value_heads": 8, "vocab_size": 32000,
                    "rms_norm_eps": 1e-5, "rope_theta": 1e6,
                    "num_local_experts": 8, "num_experts_per_tok": 2})
    assert (mixtral["hd"], mixtral["experts"], mixtral["top_k"]) == \
        (128, 8, 2)
    assert roofline.linear_params(mixtral) == 12_617_515_008 == \
        32 * (41_943_040 + 352_321_536 + 32_768)
    # dense configurations count as before: Qwen3-4B's 36 layers of
    # 41.9M attention and 74.7M MLP weights
    q4 = _file_dims("qwen3-4b.w3g128.json")
    assert (q4["experts"], q4["top_k"]) == (0, 0)
    assert roofline.linear_params(q4) == 3_633_315_840 == \
        36 * (2560 * 48 * 128 + 32 * 128 * 2560 + 3 * 2560 * 9728)


def test_expert_layers_draw_a_stack_per_expert_and_a_router():
    from chipbench.lib.weights import dims, layer_weights, root_key
    c = {"hidden_size": 64, "intermediate_size": 256,
         "num_hidden_layers": 1, "num_attention_heads": 4,
         "num_key_value_heads": 2, "vocab_size": 128, "rms_norm_eps": 1e-5,
         "rope_theta": 1e6, "num_local_experts": 8, "num_experts_per_tok": 2}
    w = layer_weights(root_key(3), 0, dims(c))
    assert w["wg"].shape == w["wu"].shape == (8, 64, 256)
    assert w["wd"].shape == (8, 256, 64) and w["router"].shape == (64, 8)
    # N(0, 1/K) along each expert's own K
    for n, K in (("wg", 64), ("wd", 256)):
        assert float(jnp.std(w[n].astype(jnp.float32))) == \
            pytest.approx(K ** -0.5, rel=0.05)
    dense = layer_weights(root_key(3), 0, dims(
        {k: v for k, v in c.items() if "expert" not in k}))
    assert dense["wg"].shape == (64, 256) and "router" not in dense


# -------------------------------------------------------------- traffic

def test_traffic_repeats_per_seed_and_keeps_sizes_across_seeds():
    m = tiny.mix("open", prefixes=True)
    a = Traffic(m, 512, 7).block(3)
    b = Traffic(m, 512, 7).block(3)
    c = Traffic(m, 512, 8).block(3)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.gap_s == y.gap_s for x, y in zip(a, b))
    # another seed: the same sizes, gaps and prefixes in the same
    # order, other token ids
    assert [(len(x.prompt), x.max_new, x.gap_s, x.prefix) for x in a] == \
        [(len(x.prompt), x.max_new, x.gap_s, x.prefix) for x in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    for blk in (a, c, Traffic(m, 512, 8).block(0)):
        assert sorted(len(x.prompt) for x in blk) == \
            sorted(len(x.prompt) for x in a)
        assert sorted(x.max_new for x in blk) == sorted(x.max_new for x in a)
        assert sorted(x.gap_s for x in blk) == sorted(x.gap_s for x in a)


def test_traffic_matches_its_declared_distributions():
    spec = {"median": 128, "sigma": 0.6, "min": 32, "max": 512}
    s = quantile_sizes(spec, 1000)
    assert abs(np.median(s) - 128) <= 1
    assert s.min() >= 32 and s.max() <= 512
    # log-normal: the middle half spans exp(+-0.674 sigma) of the median
    q1, q3 = np.percentile(s, [25, 75])
    assert q1 == pytest.approx(128 * np.exp(-0.674 * 0.6), rel=0.02)
    assert q3 == pytest.approx(128 * np.exp(0.674 * 0.6), rel=0.02)
    m = dict(tiny.mix("open", prefixes=True), block=100, rate=16.0)
    t = Traffic(m, 512, 3)
    blk = t.block(0)
    assert np.mean([x.gap_s for x in blk]) == pytest.approx(1 / 16, rel=0.05)
    counts = np.bincount([x.prefix for x in blk])
    assert list(counts) == [70, 30]
    for x in blk:
        assert np.array_equal(x.prompt[:64], t.prefixes[x.prefix])


def test_first_token_after_the_prefix_is_unique_per_request():
    m = dict(tiny.mix("closed", prefixes=True), block=64)
    t = Traffic(m, 151936, 2 ** 40 + 9)
    firsts = [x.prompt[64] for b in range(5) for x in t.block(b)]
    assert len(set(firsts)) == len(firsts)


# ------------------------------------------------------------ reference

def _whole_model_logits(config, seed, tokens):
    """The same reference, written as one whole-model forward with full
    logits (no layer loop, no row blocks, no head chunks)."""
    from chipbench.configs import qwen3 as ref
    from chipbench.lib.weights import (ATTN, MLP, dims, embedding,
                                       layer_weights, root_key)
    dm = dims(config)
    key = root_key(seed)
    q = config["quant"]
    e, fl = embedding(key, dm)
    e = e.astype(jnp.float32)
    S = tokens.shape[1]
    x = e[tokens]
    pad = (-S) % ref.Q_CHUNK
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    for i in range(dm["layers"]):
        raw = layer_weights(key, i, dm)
        w = {n: ref.code(raw[n], q["bits"], q["group_size"])
             for n in ATTN + MLP}
        g = {n: 1.0 + raw[n].astype(jnp.float32)
             for n in ("ln", "ln2", "qn", "kn")}
        x = ref.block(x, w, g, dm, ref._same)
    x = x[:, :S]
    return ref.rms(x, 1.0 + fl.astype(jnp.float32), dm["eps"]) @ e.T


def test_layer_by_layer_reference_equals_whole_model_forward():
    from chipbench.configs import qwen3 as ref
    config = tiny.config()
    rng = np.random.default_rng(0)
    rows = [(rng.integers(0, 512, n).astype(np.int32), s)
            for n, s in ((40, 20), (75, 30), (12, 5))]
    with jax.default_matmul_precision("highest"):
        got = ref.score(config, 123, rows)
        for (seq, start), g in zip(rows, got):
            lg = np.asarray(_whole_model_logits(
                config, 123, jnp.asarray(seq[None, :-1])))[0]
            pos = np.arange(start, len(seq) - 1)
            np.testing.assert_allclose(g["best"], lg[pos].max(-1),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(
                g["served"], lg[pos, seq[pos + 1]], rtol=1e-5, atol=1e-5)


def test_reference_matches_the_program_forward_at_float32():
    """The reference and the program's own float32 forward over the
    program's coded weights agree: the served format holds what the
    reference codes, and both compute the same architecture."""
    from chipbench.configs import qwen3 as ref
    from chipbench.lib.spec import program_module
    from repro.models import forward
    from repro.quant import QuantizedTensor
    config = tiny.config()
    prog = program_module(config)
    cfg = prog.model_config(config).replace(dtype="float32")
    params = prog.params(5, config, None)
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    p32 = jax.tree.map(lambda x: x.dequant(jnp.float32) if is_qt(x)
                       else x.astype(jnp.float32), params, is_leaf=is_qt)
    seq = np.random.default_rng(1).integers(0, 512, 50).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(forward(cfg, p32, jnp.asarray(seq[None, :-1]),
                                remat="none")[0])[0]
        got = ref.score(config, 5, [(seq, 10)])[0]
    pos = np.arange(10, 49)
    np.testing.assert_allclose(got["best"], lg[pos].max(-1), atol=2e-4)
    np.testing.assert_allclose(got["served"], lg[pos, seq[pos + 1]],
                               atol=2e-4)


# ------------------------------------------------------------- refusal

def test_end_to_end_hand_worked():
    """Token gaps count where their later token is in the window; the
    mean is over every such gap, the rate over tokens in the window."""
    from types import SimpleNamespace as N
    import chipbench.run as run
    tracks = [N(due=1.0, t_first=1.5, t_tokens=[1.5, 1.6, 1.9]),
              N(due=-1.0, t_first=-0.5, t_tokens=[-0.5, 0.2, 0.3]),
              N(due=9.0, t_first=9.5, t_tokens=[9.5, 10.5])]
    e = run.end_to_end(N(t0=0.0, t1=10.0, tracks=tracks), {})
    gaps_ms = [100.0, 300.0, 700.0, 100.0]      # 10.5 falls after t1
    assert e["n_itl"] == 4
    assert e["itl_mean_ms"] == pytest.approx(sum(gaps_ms) / 4)
    assert e["itl_p95_ms"] == pytest.approx(np.percentile(gaps_ms, 95))
    assert e["tok_s"] == pytest.approx(6 / 10.0)
    assert e["n_due"] == 2
    assert e["ttft_p95_ms"] == pytest.approx(np.percentile([500, 500], 95))
    assert run.end_to_end(N(t0=0.0, t1=1.0, tracks=[]), {})[
        "itl_mean_ms"] is None


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-4b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
