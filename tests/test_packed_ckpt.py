"""Packed-artifact round trips (repro/ckpt/packed.py) and the serving
follow-ups: save -> load bit-exactness, load-quantized boot producing
token-identical output without re-quantizing, device-resident block
tables, and the radix prefix-index page cap."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.packed import load_packed, save_packed
from repro.configs import get_config
from repro.core import quantize_model
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.quant import OverrideRule, QuantSpec, QuantizedTensor
from repro.serve import PagedKVCache, RadixPrefixCache, Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _tiny():
    cfg = get_config("tiny-lm").replace(dtype="float32", n_layers=2)
    p = init_params(cfg, KEY)
    calib = [jax.random.randint(jax.random.fold_in(KEY, i), (2, 48), 0,
                                cfg.vocab_size) for i in range(2)]
    return cfg, p, calib


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))[0]


# ---------------------------------------------------------------------------
# save -> load
# ---------------------------------------------------------------------------

def test_packed_roundtrip_is_bit_exact(tmp_path):
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed",
                                 overrides=(OverrideRule("wv", bits=2),))
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    save_packed(tmp_path / "m", qp, spec=spec, meta={"arch": "tiny-lm"})
    lp, lspec, meta = load_packed(tmp_path / "m")
    assert lspec == spec and meta["arch"] == "tiny-lm"
    flat_q, flat_l = _leaves(qp), _leaves(lp)
    assert len(flat_q) == len(flat_l)
    for (path_q, leaf_q), (path_l, leaf_l) in zip(flat_q, flat_l):
        assert path_q == path_l
        if isinstance(leaf_q, QuantizedTensor):
            assert isinstance(leaf_l, QuantizedTensor)
            assert leaf_l.k_in == leaf_q.k_in
            assert leaf_l.orig_dtype == leaf_q.orig_dtype
            for f in ("codes", "alphas", "betas"):
                a, b = getattr(leaf_q, f), getattr(leaf_l, f)
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert leaf_q.dtype == leaf_l.dtype
            np.testing.assert_array_equal(np.asarray(leaf_q),
                                          np.asarray(leaf_l))


def test_bf16_leaves_roundtrip(tmp_path):
    import jax.numpy as jnp
    tree = {"w": jnp.asarray(np.linspace(-2, 2, 16), jnp.bfloat16)}
    save_packed(tmp_path / "b", tree)
    out, spec, _ = load_packed(tmp_path / "b")
    assert spec is None
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["w"].view(jnp.uint16)),
        np.asarray(tree["w"].view(jnp.uint16)))


def test_uncommitted_artifact_is_rejected(tmp_path):
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d = save_packed(tmp_path / "m", qp, spec=spec)
    (d / "COMMITTED").unlink()
    with pytest.raises(FileNotFoundError, match="COMMITTED"):
        load_packed(d)


def test_loaded_model_serves_identically(tmp_path):
    """--save-quantized / --load-quantized contract: the reloaded packed
    model skips calibration/GPTQ and serves token-identical output."""
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    save_packed(tmp_path / "m", qp, spec=spec)
    lp, _, _ = load_packed(tmp_path / "m")

    mk = lambda: [Request(prompt=(np.arange(10) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=8)
                  for i in range(2)]
    outs = []
    for params in (qp, lp):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          dtype="float32")
        reqs = mk()
        eng.run(reqs)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# group-wise (G > 1) artifacts
# ---------------------------------------------------------------------------

def test_grouped_packed_roundtrip_and_serving(tmp_path):
    """A G>1 QuantizedTensor tree survives save/load bit-exactly and
    serves token-identically (the PR 3 round-trip, with groups)."""
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed",
                                 group_size=64)
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    # the tree really carries grouped scale leaves
    qts = [l for _, l in _leaves(qp) if isinstance(l, QuantizedTensor)]
    assert qts and all(q.n_groups == q.k_in // 64 for q in qts)
    assert any(q.n_groups > 1 for q in qts)
    save_packed(tmp_path / "g", qp, spec=spec, meta={"arch": "tiny-lm"})
    lp, lspec, _ = load_packed(tmp_path / "g")
    assert lspec.group_size == 64
    for (pq, lq), (pl_, ll) in zip(_leaves(qp), _leaves(lp)):
        assert pq == pl_
        if isinstance(lq, QuantizedTensor):
            assert ll.n_groups == lq.n_groups
            assert ll.group_size == lq.group_size
            for f in ("codes", "alphas", "betas"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(lq, f)), np.asarray(getattr(ll, f)))

    mk = lambda: [Request(prompt=(np.arange(10) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=8)
                  for i in range(2)]
    outs = []
    for params in (qp, lp):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          dtype="float32")
        reqs = mk()
        eng.run(reqs)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1]


def test_manifest_records_group_axis(tmp_path):
    import json
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed",
                                 group_size=128)
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d = save_packed(tmp_path / "m", qp, spec=spec)
    manifest = json.loads((d / "manifest.json").read_text())
    wq = manifest["tree"]["blocks"]["L0"]["attn"]["wq"]
    assert wq["kind"] == "qt"
    assert wq["group_size"] == 128
    assert wq["groups"] == wq["k_in"] // 128


def test_legacy_g1_artifact_warns_under_grouped_spec(tmp_path):
    """A pre-groups artifact (spec carries group_size but leaves are
    per-channel) must warn exactly once on load."""
    import warnings as _w

    from repro.ckpt import packed as packed_mod
    cfg, p, calib = _tiny()
    # simulate the legacy state: solvers ignored group_size -> G=1 leaves
    # but the spec recorded in the manifest still requests groups
    spec_g1 = QuantSpec.from_config(cfg.quant, method="gptqt",
                                    mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec_g1)
    legacy_spec = spec_g1.replace(group_size=64)
    save_packed(tmp_path / "legacy", qp, spec=legacy_spec)
    packed_mod._WARNED_LEGACY_GROUPS = False
    with pytest.warns(UserWarning, match="per-channel"):
        load_packed(tmp_path / "legacy")
    with _w.catch_warnings():           # one-time: second load is silent
        _w.simplefilter("error")
        load_packed(tmp_path / "legacy")
    packed_mod._WARNED_LEGACY_GROUPS = False


# ---------------------------------------------------------------------------
# manifest v3: sharding metadata, bf16 scales, v2 back-compat
# ---------------------------------------------------------------------------

def test_manifest_v3_records_symbolic_shardings(tmp_path):
    """Every leaf entry carries a symbolic PartitionSpec (axis names, no
    sizes) so any later mesh can place it without re-deriving the rules;
    QT children follow the dense weight they replace."""
    import json
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d = save_packed(tmp_path / "m", qp, spec=spec)
    m = json.loads((d / "manifest.json").read_text())
    assert m["format_version"] == 4
    assert m["sharding"]["axes"] == ["data", "model"]
    wq = m["tree"]["blocks"]["L0"]["attn"]["wq"]
    assert wq["pspec"]["codes"][-2:] == ["data", "model"]
    assert wq["pspec"]["alphas"][-3:] == [None, "model", None]
    assert wq["pspec"]["betas"][-1] == "model"
    ln = m["tree"]["blocks"]["L0"]["ln"]
    assert all(a is None for a in ln["pspec"])   # norms replicate


def test_v2_artifact_loads_and_warns_on_mesh(tmp_path):
    """A v2 manifest (pre-sharding-metadata) must keep loading; with a
    mesh it can only replicate, and says so once."""
    import json
    import warnings as _w

    from repro.ckpt import packed as packed_mod
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d = save_packed(tmp_path / "m", qp, spec=spec)

    # strip the artifact back to v2: no sharding block, no pspec keys
    m = json.loads((d / "manifest.json").read_text())
    m["format_version"] = 2
    m.pop("sharding")

    def strip(node):
        if isinstance(node.get("kind"), str):
            node.pop("pspec", None)
            return
        for v in node.values():
            strip(v)
    strip(m["tree"])
    (d / "manifest.json").write_text(json.dumps(m))

    lp, lspec, _ = load_packed(d)          # meshless load: bit-exact
    for (pq, lq), (pl_, ll) in zip(_leaves(qp), _leaves(lp)):
        if isinstance(lq, QuantizedTensor):
            np.testing.assert_array_equal(np.asarray(lq.codes),
                                          np.asarray(ll.codes))
    mesh = make_host_mesh()
    packed_mod._WARNED_NO_PSPEC = False
    with pytest.warns(UserWarning, match="REPLICATED"):
        load_packed(d, mesh=mesh)
    with _w.catch_warnings():              # one-time warning
        _w.simplefilter("error")
        load_packed(d, mesh=mesh)
    packed_mod._WARNED_NO_PSPEC = False


def test_future_format_is_refused(tmp_path):
    import json
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d = save_packed(tmp_path / "m", qp, spec=spec)
    m = json.loads((d / "manifest.json").read_text())
    m["format_version"] = 99
    (d / "manifest.json").write_text(json.dumps(m))
    with pytest.raises(ValueError, match="newer"):
        load_packed(d)


def test_bf16_scales_halve_bytes_and_stay_within_tolerance(tmp_path):
    """scale_dtype='bfloat16' stores alphas/betas as bf16 bits (half the
    scale bytes of the G>1 overhead), loads back STILL bf16 in memory
    (the decode expand paths upcast per-tile, so fp32 rehydration on
    load would only double resident scale bytes), and serves
    token-identically to an engine fed the same-rounded scales
    directly."""
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed",
                                 group_size=64)
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d32 = save_packed(tmp_path / "f32", qp, spec=spec)
    d16 = save_packed(tmp_path / "bf16", qp, spec=spec,
                      scale_dtype="bfloat16")

    import json
    a32 = np.load(d32 / "arrays.npz")
    a16 = np.load(d16 / "arrays.npz")
    wq32 = json.loads((d32 / "manifest.json").read_text())[
        "tree"]["blocks"]["L0"]["attn"]["wq"]
    wq16 = json.loads((d16 / "manifest.json").read_text())[
        "tree"]["blocks"]["L0"]["attn"]["wq"]
    assert wq16["scale_dtype"] == "bfloat16" and "scale_dtype" not in wq32
    for f in ("alphas", "betas"):       # stored bytes exactly halved
        assert a16[wq16[f]].dtype == np.uint16
        assert a16[wq16[f]].nbytes * 2 == a32[wq32[f]].nbytes
    assert a16[wq16["codes"]].dtype == np.uint32   # codes untouched

    lp, lspec, _ = load_packed(d16)
    assert lspec.group_size == 64
    for (_, lq), (_, ll) in zip(_leaves(qp), _leaves(lp)):
        if not isinstance(lq, QuantizedTensor):
            continue
        # scales stay bf16 in memory — no fp32 rehydration on load
        assert ll.alphas.dtype == jnp.bfloat16
        assert ll.betas.dtype == jnp.bfloat16
        # exactly one bf16 rounding, no double rounding
        ref = lq.cast_scales("bfloat16")
        np.testing.assert_array_equal(np.asarray(ll.alphas),
                                      np.asarray(ref.alphas))
        np.testing.assert_array_equal(np.asarray(ll.betas),
                                      np.asarray(ref.betas))
        ll = ll.cast_scales("float32")             # for the rel check
        # and the rounding is small: bf16 keeps ~8 mantissa bits
        denom = np.abs(np.asarray(lq.alphas)) + 1e-8
        rel = np.abs(np.asarray(ll.alphas) - np.asarray(lq.alphas)) / denom
        assert float(rel.max()) < 1 / 128

    mk = lambda: [Request(prompt=(np.arange(10) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=8)
                  for i in range(2)]
    rounded = jax.tree.map(
        lambda x: (x.cast_scales("bfloat16").cast_scales("float32")
                   if isinstance(x, QuantizedTensor) else x), qp,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
    outs = []
    for params in (rounded, lp):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          dtype="float32")
        reqs = mk()
        eng.run(reqs)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1]


def test_already_bf16_scales_save_loadable(tmp_path):
    """A tree whose QT scales are ALREADY bf16 (cast_scales) must not
    commit an unreadable artifact: npz would degrade bf16 to a void
    dtype, so save_packed stores the bits + flags the leaf even without
    an explicit scale_dtype."""
    import jax.numpy as jnp
    from repro.quant.packing import pack_signs
    rng = np.random.default_rng(0)
    signs = jnp.asarray(np.sign(rng.standard_normal((2, 32, 8))) + 0.0)
    qt = QuantizedTensor(
        codes=pack_signs(signs),
        alphas=jnp.asarray(rng.standard_normal((1, 8, 2)), jnp.float32),
        betas=jnp.asarray(rng.standard_normal((1, 8)), jnp.float32),
        k_in=32).cast_scales("bfloat16")
    d = save_packed(tmp_path / "m", {"w": qt})
    lp, _, _ = load_packed(d)           # must not raise
    assert lp["w"].alphas.dtype == jnp.bfloat16    # stays bf16 in memory
    np.testing.assert_array_equal(
        np.asarray(lp["w"].alphas.astype(jnp.float32)),
        np.asarray(qt.alphas.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# manifest v4: optional draft-scale block
# ---------------------------------------------------------------------------

def test_v4_draft_block_roundtrips_refit_scales(tmp_path):
    """save_packed(draft_bits=d) stores per-leaf re-fit draft scales as
    the manifest-v4 optional block; load_draft_scales returns them
    bit-exact to the on-the-fly refit, so a --speculate boot from the
    artifact builds the identical draft tree without the solve. An
    artifact saved without the block returns None (v3-style fallback)."""
    import json

    from repro.ckpt.packed import load_draft_scales
    from repro.quant.draft import make_draft_params
    cfg, p, calib = _tiny()
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    d = save_packed(tmp_path / "m", qp, spec=spec, draft_bits=2)
    assert load_draft_scales(
        save_packed(tmp_path / "plain", qp, spec=spec)) is None

    m = json.loads((d / "manifest.json").read_text())
    assert m["format_version"] == 4 and m["draft_bits"] == 2
    wq = m["tree"]["blocks"]["L0"]["attn"]["wq"]
    assert wq["draft"]["bits"] == 2

    lp, _, _ = load_packed(d)
    tree = load_draft_scales(d)
    assert tree is not None
    from_block = make_draft_params(lp, 2, tree)
    refit = make_draft_params(lp, 2)
    for (path, a), (_, b) in zip(_leaves(from_block), _leaves(refit)):
        if not isinstance(a, QuantizedTensor):
            continue
        assert a.bits == 2 and a.stored_bits == 3
        assert a.codes is b.codes            # shared sign planes
        np.testing.assert_array_equal(np.asarray(a.alphas),
                                      np.asarray(b.alphas))
        np.testing.assert_array_equal(np.asarray(a.betas),
                                      np.asarray(b.betas))
    # mismatched draft_bits must ignore the stored block, not misuse it
    w3 = make_draft_params(lp, 1, tree)
    for _, leaf in _leaves(w3):
        if isinstance(leaf, QuantizedTensor):
            assert leaf.bits == 1


# ---------------------------------------------------------------------------
# device-resident block tables
# ---------------------------------------------------------------------------

def test_device_block_tables_track_host_incrementally():
    cfg = get_config("tiny-lm").replace(dtype="float32", n_layers=2,
                                        d_model=64, d_ff=128, remat="none")
    p = init_params(cfg, KEY)
    mk = lambda: [Request(prompt=(np.arange(12) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=8)
                  for i in range(4)]
    dense = ServeEngine(cfg, p, batch_size=2, max_len=64, dtype="float32")
    want = mk()
    dense.run(want)
    eng = ServeEngine(cfg, p, batch_size=2, max_len=64, dtype="float32",
                      cache_kind="paged", page_size=8)
    got = mk()
    eng.run(got)
    assert [r.out for r in got] == [r.out for r in want]
    # the mirror converges to the host tables after a sync, and rows the
    # allocator never touched since the last sync are not re-uploaded
    eng._sync_block_tables()
    np.testing.assert_array_equal(np.asarray(eng._bt_dev),
                                  eng.kv.block_tables)
    applied = eng._bt_applied.copy()
    eng._sync_block_tables()            # no version moved -> no-op
    np.testing.assert_array_equal(applied, eng._bt_applied)


def test_bt_versions_bump_on_every_mutation():
    kv = PagedKVCache(None, n_pages=9, page_size=4, max_seqs=2,
                      create_pool=False)
    s = kv.alloc_slot()
    v0 = kv.bt_version[s]
    kv.ensure(s, 6)
    assert kv.bt_version[s] > v0
    v1 = kv.bt_version[s]
    kv.ensure(s, 6)                     # no growth -> no bump
    assert kv.bt_version[s] == v1
    s2 = kv.alloc_slot()
    kv.share(s2, kv.owned_pages(s)[:1])
    assert kv.bt_version[s2] > 0
    v2 = kv.bt_version[s2]
    kv.cow_for_write(s2, 0, 2)          # forks the shared page
    assert kv.bt_version[s2] > v2
    v3 = kv.bt_version[s]
    kv.release(s)
    assert kv.bt_version[s] > v3


# ---------------------------------------------------------------------------
# radix prefix-index page cap
# ---------------------------------------------------------------------------

def test_prefix_index_cap_bounds_retained_pages():
    kv = PagedKVCache(None, n_pages=33, page_size=4, max_seqs=4,
                      create_pool=False)
    idx = RadixPrefixCache(kv, max_cached_pages=6)
    for i in range(10):                 # 10 distinct 8-token prefixes
        s = kv.alloc_slot()
        kv.ensure(s, 8)
        idx.insert(np.arange(8) + 100 * i, kv.owned_pages(s))
        kv.release(s)
        assert idx.cached_pages() <= 6
        assert idx.cached_pages() == idx._count_nodes()
    assert idx.evictions >= 8           # 20 inserted pages, 6 kept
    # conservation holds through cap eviction
    assert kv.live_pages + kv.free_page_count == kv.usable_pages
    assert kv.live_pages == idx.cached_pages()


def test_prefix_cap_never_evicts_pages_referenced_by_sequences():
    kv = PagedKVCache(None, n_pages=9, page_size=4, max_seqs=2,
                      create_pool=False)
    idx = RadixPrefixCache(kv, max_cached_pages=1)
    s = kv.alloc_slot()
    kv.ensure(s, 8)
    idx.insert(np.arange(8), kv.owned_pages(s))   # slot still holds refs
    # over cap, but both pages are pinned by the running sequence
    assert idx.cached_pages() == 2
    assert idx.lookup(np.arange(8))[0] == 8
    kv.release(s)                       # now index-only ...
    s2 = kv.alloc_slot()
    kv.ensure(s2, 4)
    idx.insert(np.asarray([50, 51, 52, 53]), kv.owned_pages(s2))
    kv.release(s2)
    assert idx.cached_pages() <= 1      # ... and the next insert enforces


def test_engine_default_cap_leaves_slot_headroom():
    cfg = get_config("tiny-lm").replace(dtype="float32", n_layers=2,
                                        d_model=64, d_ff=128, remat="none")
    p = init_params(cfg, KEY)
    eng = ServeEngine(cfg, p, batch_size=2, max_len=64, dtype="float32",
                      cache_kind="paged", page_size=8)
    assert eng._prefix.max_cached_pages == eng.kv.usable_pages - 2
    eng2 = ServeEngine(cfg, p, batch_size=2, max_len=64, dtype="float32",
                       cache_kind="paged", page_size=8, prefix_max_pages=3)
    assert eng2._prefix.max_cached_pages == 3
    reqs = [Request(prompt=(np.arange(20) + 13 * i).astype(np.int32)
                    % cfg.vocab_size, max_new_tokens=4) for i in range(5)]
    eng2.run(reqs)
    assert all(r.done for r in reqs)
    assert eng2._prefix.cached_pages() <= 3
