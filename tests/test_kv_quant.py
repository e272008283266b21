"""Binary-coded KV cache: coding round-trip, fused-dequant kernel vs
oracle, bytes accounting, COW forks on quantized pages, and greedy
equality of the quantized pool against the raw fp pool on the trained
toy model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_quant
from repro.models.attention import paged_kv_page_bytes
from repro.models.model import (PAGE_LEAVES, copy_pages, init_paged_cache,
                                map_page_leaves)
from repro.quant.kv import (kv_bytes_per_token_head, kv_dequantize,
                            kv_layout, kv_pool_rows, kv_pool_views,
                            kv_quantize)

KEY = jax.random.PRNGKey(0)


def page_leaves(cache):
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
            if path[-1].key in PAGE_LEAVES]


def _tiny_cfg():
    return get_config("tiny-lm").replace(dtype="float32", n_layers=2,
                                         d_model=64, d_ff=128, remat="none")


# ---------------------------------------------------------------------------
# coding round-trip
# ---------------------------------------------------------------------------

def _rel_err(x, bits, **kw):
    y = kv_dequantize(*kv_quantize(x, bits, **kw))
    return float(jnp.linalg.norm(x - y) / jnp.linalg.norm(x))


def test_kv_roundtrip_error_decays_with_bits():
    x = jax.random.normal(KEY, (32, 2, 64), jnp.float32)
    errs = [_rel_err(x, b) for b in (1, 2, 4, 8)]
    assert all(a > b for a, b in zip(errs, errs[1:])), errs
    # the alternating refinement keeps per-bit decay going where pure
    # greedy coding plateaus around 10% — 4 bits must land well below
    assert errs[2] < 0.15 and errs[3] < 0.06, errs


def test_kv_refinement_beats_greedy():
    x = jax.random.normal(KEY, (64, 64), jnp.float32)
    greedy = _rel_err(x, 4, iters=0)
    refined = _rel_err(x, 4)
    assert refined < greedy - 0.02, (greedy, refined)


def test_kv_roundtrip_grouped_scales():
    x = jax.random.normal(KEY, (16, 64), jnp.float32) * \
        jnp.linspace(0.1, 10.0, 64)          # scale varies along head_dim
    whole = _rel_err(x, 2)
    grouped = _rel_err(x, 2, kv_group_size=16)
    assert grouped < whole                   # finer scales fit the ramp


def test_kv_quantize_shapes_and_dtypes():
    x = jax.random.normal(KEY, (3, 5, 64), jnp.float32)
    codes, alphas, betas = kv_quantize(x, 4, kv_group_size=32)
    assert codes.shape == (3, 5, 4, 2) and codes.dtype == jnp.uint32
    assert alphas.shape == (3, 5, 2, 4) and alphas.dtype == jnp.float32
    assert betas.shape == (3, 5, 2) and betas.dtype == jnp.float32


def test_kv_pool_rows_roundtrip():
    """Page pools store one lane-dense row per token, heads outermost;
    the per-head views come back bit-identical."""
    x = jax.random.normal(KEY, (3, 5, 4, 64), jnp.float32)   # Hkv = 4
    coded = kv_quantize(x, 3, kv_group_size=32)
    rows = kv_pool_rows(*coded)
    assert [r.shape for r in rows] == [(3, 5, 24), (3, 5, 24), (3, 5, 8)]
    for a, b in zip(kv_pool_views(*rows, n_kv_heads=4), coded):
        assert a.shape == b.shape and bool((a == b).all())


def test_kv_layout_validation():
    assert kv_layout(64, 4) == (1, 2)
    assert kv_layout(64, 2, 16) == (4, 2)
    with pytest.raises(ValueError):
        kv_layout(64, 0)                     # bits < 1
    with pytest.raises(ValueError):
        kv_layout(48, 4)                     # head_dim % 32 != 0
    with pytest.raises(ValueError):
        kv_layout(64, 4, kv_group_size=24)   # group doesn't divide hd


def test_kv_bytes_per_token_head():
    assert kv_bytes_per_token_head(64, 0) == 256          # raw fp32
    assert kv_bytes_per_token_head(64, 0, dtype_itemsize=2) == 128
    assert kv_bytes_per_token_head(64, 4) == 52           # 4.9x vs fp32
    assert kv_bytes_per_token_head(64, 1) == 16
    # must agree with the actual device pool, leaf by leaf
    cfg = _tiny_cfg()
    for bits in (0, 4):
        cache = init_paged_cache(cfg, n_pages=6, page_size=8, max_seqs=2,
                                 kv_bits=bits)
        leaves = page_leaves(cache)
        assert sum(l.nbytes for l in leaves) // 6 \
            == paged_kv_page_bytes(cfg, 8, "float32", kv_bits=bits)


# ---------------------------------------------------------------------------
# fused-dequant kernel vs oracle
# ---------------------------------------------------------------------------

def _quant_pool(rng, P, page, Hkv, hd, bits, L=1):
    """Coded K/V pool leaves, stacked over L layers."""
    k = jnp.asarray(rng.standard_normal((L, P, page, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, P, page, Hkv, hd)), jnp.float32)
    # iters=1 keeps the sweep fast; kernel parity is about consuming the
    # codes, not about how well they were fitted
    return (kv_pool_rows(*kv_quantize(k, bits, iters=1))
            + kv_pool_rows(*kv_quantize(v, bits, iters=1)))


@pytest.mark.parametrize("page,bits", [(8, 1), (8, 4), (16, 2), (16, 4),
                                       (32, 3)])
def test_quant_kernel_matches_oracle_sweep(page, bits):
    """Kernel vs jnp oracle across page sizes x kv_bits with ragged
    context lengths straddling page boundaries. Both sides consume the
    same codes, so the tolerance is fp32-accumulation noise, not coding
    error."""
    rng = np.random.default_rng(page * 31 + bits)
    Hkv, rep, hd, T = 2, 2, 64, 4
    P = T + 3
    ctx = [1, page - 1, page, page + 1, T * page]
    B = len(ctx)
    q = jnp.asarray(rng.standard_normal((B, Hkv, rep, hd)), jnp.float32)
    pool = _quant_pool(rng, P, page, Hkv, hd, bits)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray(ctx, jnp.int32)
    want = ref.paged_attention_quant_ref(q, *pool, bt, ctx, 0)
    got = paged_attention_quant(q, *pool, bt, ctx, 0, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("window,cap", [(10, None), (None, 30.0),
                                        (7, 50.0)])
def test_quant_kernel_matches_oracle_window_cap(window, cap):
    rng = np.random.default_rng(7)
    B, Hkv, rep, hd, P, page, T = 3, 2, 2, 64, 7, 16, 4
    q = jnp.asarray(rng.standard_normal((B, Hkv, rep, hd)), jnp.float32)
    pool = _quant_pool(rng, P, page, Hkv, hd, 4)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray([1, 17, T * page], jnp.int32)
    want = ref.paged_attention_quant_ref(q, *pool, bt, ctx, 0,
                                         window=window, cap=cap)
    got = paged_attention_quant(q, *pool, bt, ctx, 0, window=window,
                                cap=cap, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_quant_kernel_reads_its_layer_of_the_stack(layer):
    """On a stacked coded pool whose layers differ, the kernel at
    `layer` matches the oracle at `layer`, which is the one-layer oracle
    on that layer's codes and scales, and differs from every other
    layer's."""
    rng = np.random.default_rng(5)
    L, B, Hkv, rep, hd, P, page, T = 4, 3, 2, 2, 64, 6, 8, 3
    q = jnp.asarray(rng.standard_normal((B, Hkv, rep, hd)), jnp.float32)
    pool = _quant_pool(rng, P, page, Hkv, hd, 4, L=L)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray([2, 13, T * page], jnp.int32)
    got = paged_attention_quant(q, *pool, bt, ctx, jnp.int32(layer),
                                interpret=True)
    want = ref.paged_attention_quant_ref(q, *pool, bt, ctx, layer)
    alone = ref.paged_attention_quant_ref(
        q, *(a[layer:layer + 1] for a in pool), bt, ctx, 0)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want - alone).max()) == 0.0
    for other in set(range(L)) - {layer}:
        wrong = ref.paged_attention_quant_ref(q, *pool, bt, ctx, other)
        assert float(jnp.abs(got - wrong).max()) > 1e-2


def test_quant_oracle_approaches_fp_oracle_with_bits():
    """At 8 bits the dequantized pool attends like the raw pool."""
    rng = np.random.default_rng(3)
    B, Hkv, rep, hd, P, page, T = 3, 2, 2, 64, 6, 8, 3
    q = jnp.asarray(rng.standard_normal((B, Hkv, rep, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((1, P, page, Hkv, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((1, P, page, Hkv, hd)),
                     jnp.float32)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray([1, 10, T * page], jnp.int32)
    want = ref.paged_attention_ref(q, kp, vp, bt, ctx, 0)
    errs = []
    for bits in (2, 4, 8):
        pool = (kv_pool_rows(*kv_quantize(kp, bits))
                + kv_pool_rows(*kv_quantize(vp, bits)))
        got = ref.paged_attention_quant_ref(q, *pool, bt, ctx, 0)
        errs.append(float(jnp.abs(got - want).max()))
    # random N(0,1) K/V is the adversarial case (softmax amplifies any
    # coding error), so gate the decay, not a small absolute bound
    assert errs[0] > errs[1] > errs[2], errs
    assert errs[2] < errs[0] / 3, errs


# ---------------------------------------------------------------------------
# COW fork on quantized pages
# ---------------------------------------------------------------------------

def test_copy_pages_moves_codes_and_scales():
    """A COW fork on a quantized pool must copy every page leaf — sign
    codes AND alpha/beta scales; a fork that moved only the codes would
    dequantize the destination with the null page's zero scales."""
    cfg = _tiny_cfg()
    n_pages = 6
    cache = init_paged_cache(cfg, n_pages=n_pages, page_size=8, max_seqs=2,
                             kv_bits=4)
    key = KEY

    def fill(leaf):
        nonlocal key
        key, k = jax.random.split(key)
        if leaf.dtype == jnp.uint32:
            val = jax.random.randint(k, leaf[:, 2].shape, 0, 2**31 - 1,
                                     dtype=jnp.uint32)
        else:
            val = jax.random.normal(k, leaf[:, 2].shape, dtype=leaf.dtype)
        return leaf.at[:, 2].set(val)

    cache = map_page_leaves(fill, cache)
    out = copy_pages(cache, jnp.asarray([2], jnp.int32),
                     jnp.asarray([4], jnp.int32), n_pages)
    leaves = page_leaves(out)
    # k/v x codes/alphas/betas (layers stack along the scan-group axis)
    assert len(leaves) == 6
    for leaf in leaves:
        assert bool((leaf[:, 2] == leaf[:, 4]).all())
        # the source page was random, so a dst full of zeros means the
        # copy silently skipped this leaf
        assert float(jnp.abs(leaf[:, 4].astype(jnp.float32)).sum()) > 0


# ---------------------------------------------------------------------------
# end-to-end: quantized pool vs fp pool on the trained toy model
# ---------------------------------------------------------------------------

def _trained():
    from repro.data.pretrained import get_trained_lm
    return get_trained_lm("tiny-lm", steps=40)


def _serve(cfg, params, prompts, *, kv_bits, prefix_sharing=False,
           max_new=10):
    from repro.data import ByteTokenizer
    from repro.serve import Request, ServeEngine
    tok = ByteTokenizer()
    eng = ServeEngine(cfg, params, batch_size=2, max_len=160,
                      dtype="float32", cache_kind="paged", page_size=16,
                      kv_bits=kv_bits, prefix_sharing=prefix_sharing)
    reqs = [Request(prompt=tok.encode(p), max_new_tokens=max_new)
            for p in prompts]
    eng.run(reqs)
    return [list(r.out) for r in reqs], eng


def test_quantized_greedy_matches_fp():
    """The acceptance gate: 4-bit binary-coded pages produce the same
    greedy generations as raw fp32 pages on the lightly-trained toy
    model (the model the CI serve smokes train, steps=40)."""
    cfg, params = _trained()
    prompts = ["the ancient city", "a famous museum", "this railway",
               "the council"]
    fp, _ = _serve(cfg, params, prompts, kv_bits=0)
    q4, eng = _serve(cfg, params, prompts, kv_bits=4)
    assert q4 == fp
    stats = eng.stats_snapshot()
    assert stats.kv_bits == 4
    assert stats.kv_bytes_per_page == eng.kv.bytes_per_page()
    assert stats.kv_pool_bytes == eng.kv.pool_bytes()


def test_quantized_cow_fork_end_to_end():
    """Prefix sharing + COW on a quantized pool: requests sharing a
    prompt prefix then diverging must generate exactly what they
    generate with sharing disabled — and the run must actually fork
    (cow_forks > 0), or the test is vacuous."""
    cfg, params = _trained()
    prompts = ["the ancient city walls", "the ancient city gates",
               "the ancient city was"]
    shared, eng = _serve(cfg, params, prompts, kv_bits=4,
                         prefix_sharing=True)
    unshared, _ = _serve(cfg, params, prompts, kv_bits=4)
    assert shared == unshared
    assert eng.kv.cow_forks > 0
