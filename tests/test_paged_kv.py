"""Paged-KV serving subsystem: kernel vs oracle, allocator invariants,
dense-vs-paged engine equivalence, preemption, and capacity-vs-dense."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.models import init_params
from repro.models.model import PAGE_LEAVES, init_paged_cache
from repro.serve import OutOfPages, PagedKVCache, Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _tiny_cfg():
    return get_config("tiny-lm").replace(dtype="float32", n_layers=2,
                                         d_model=64, d_ff=128, remat="none")


def _reqs(cfg, n, max_new=6, base_len=12):
    out = []
    for i in range(n):
        L = base_len + (i % 3)          # mixed prompt lengths
        out.append(Request(prompt=(np.arange(L) * 7 + i).astype(np.int32)
                           % cfg.vocab_size, max_new_tokens=max_new))
    return out


def _run(cfg, params, reqs, **kw):
    eng = ServeEngine(cfg, params, dtype="float32", **kw)
    eng.run(reqs)
    return [r.out for r in reqs], eng


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

def _pages(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


@pytest.mark.parametrize("window,cap", [(None, None), (10, None),
                                        (None, 30.0), (7, 50.0)])
def test_paged_attention_kernel_matches_ref(window, cap):
    rng = np.random.default_rng(0)
    B, Hkv, rep, hd, P, page, T = 3, 2, 4, 64, 9, 16, 4
    q = _pages(rng, B, Hkv, rep, hd)
    kp = _pages(rng, 1, P, page, Hkv, hd)       # a one-layer stack
    vp = _pages(rng, 1, P, page, Hkv, hd)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray([1, 17, T * page], jnp.int32)   # 1 token .. full
    want = ref.paged_attention_ref(q, kp, vp, bt, ctx, 0, window=window,
                                   cap=cap)
    got = paged_attention(q, kp, vp, bt, ctx, 0, window=window, cap=cap,
                          interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("page", [8, 16, 32])
def test_paged_attention_kernel_parity_page_size_sweep(page):
    """Kernel vs oracle across page sizes and ragged context lengths,
    including lengths straddling a page boundary by one token in either
    direction (the kernel's per-page masking edge)."""
    rng = np.random.default_rng(page)
    Hkv, rep, hd, T = 2, 2, 64, 4
    P = T + 3
    ctx = [1, page - 1, page, page + 1, 2 * page + 1, T * page]
    B = len(ctx)
    q = _pages(rng, B, Hkv, rep, hd)
    kp = _pages(rng, 1, P, page, Hkv, hd)
    vp = _pages(rng, 1, P, page, Hkv, hd)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray(ctx, jnp.int32)
    want = ref.paged_attention_ref(q, kp, vp, bt, ctx, 0)
    got = paged_attention(q, kp, vp, bt, ctx, 0, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("layer", [0, 2, 4])
def test_paged_attention_kernel_reads_its_layer_of_the_stack(layer):
    """On a stacked pool whose layers differ, the kernel at `layer`
    matches the oracle at `layer`, and the oracle at `layer` is the
    one-layer oracle on that layer's pool, not on any other."""
    rng = np.random.default_rng(11)
    L, B, Hkv, rep, hd, P, page, T = 5, 3, 2, 2, 64, 7, 8, 3
    q = _pages(rng, B, Hkv, rep, hd)
    kp = _pages(rng, L, P, page, Hkv, hd)
    vp = _pages(rng, L, P, page, Hkv, hd)
    bt = jnp.asarray(rng.integers(1, P, (B, T)).astype(np.int32))
    ctx = jnp.asarray([3, 12, T * page], jnp.int32)
    got = paged_attention(q, kp, vp, bt, ctx, jnp.int32(layer),
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, ctx, layer)
    alone = ref.paged_attention_ref(q, kp[layer:layer + 1],
                                    vp[layer:layer + 1], bt, ctx, 0)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want - alone).max()) == 0.0
    for other in set(range(L)) - {layer}:
        wrong = ref.paged_attention_ref(q, kp, vp, bt, ctx, other)
        assert float(jnp.abs(got - wrong).max()) > 1e-2


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def _check_invariants(kv):
    owned = [p for s in range(kv.max_seqs) for p in kv.owned_pages(s)]
    assert 0 not in owned, "null page must never be allocated"
    # refcount conservation: live pages (counted once, however many
    # rows/index nodes reference them) + free == usable
    assert kv.live_pages + kv.free_page_count == kv.usable_pages
    assert set(owned).issubset({p for p in range(kv.n_pages)
                                if kv.refcount(p) > 0})
    for s in range(kv.max_seqs):
        mine = kv.owned_pages(s)
        assert len(mine) == len(set(mine)), "page twice in one row"
        assert (kv.block_tables[s, :len(mine)] == mine).all()
        assert (kv.block_tables[s, len(mine):] == 0).all()


def test_allocator_alloc_free_invariants():
    cfg = _tiny_cfg()
    kv = PagedKVCache(cfg, n_pages=9, page_size=8, max_seqs=3,
                      max_pages_per_seq=4, dtype="float32")
    s0, s1 = kv.alloc_slot(), kv.alloc_slot()
    kv.ensure(s0, 20)                       # 3 pages
    kv.ensure(s1, 8)                        # 1 page
    _check_invariants(kv)
    assert kv.used_pages == 4 and kv.utilization() == 4 / 8
    kv.ensure(s0, 20)                       # idempotent
    assert kv.used_pages == 4
    with pytest.raises(OutOfPages):
        kv.ensure(s1, 33)                   # > max_pages_per_seq
    with pytest.raises(OutOfPages):
        s2 = kv.alloc_slot()
        kv.ensure(s2, 8 * 5)                # > free pages
    _check_invariants(kv)                   # failed ensure allocates nothing
    kv.release(s0)
    _check_invariants(kv)
    assert kv.free_page_count == 7          # only s1's single page is live
    assert kv.high_water == 4


def test_truncate_frees_trailing_pages_and_respects_sharing():
    """Speculative rollback primitive: truncate(slot, n) keeps exactly
    pages_for(n) pages, zeroes the freed block-table tail, and unrefs
    (not frees) pages another reader still holds."""
    cfg = _tiny_cfg()
    kv = PagedKVCache(cfg, n_pages=9, page_size=4, max_seqs=3,
                      max_pages_per_seq=5, dtype="float32")
    s0 = kv.alloc_slot()
    kv.ensure(s0, 18)                       # 5 pages
    v0 = kv.bt_version[s0]
    assert kv.truncate(s0, 9) == 2          # 18 -> 9 tokens: 3 pages kept
    _check_invariants(kv)
    assert len(kv.owned_pages(s0)) == 3
    assert kv.bt_version[s0] > v0           # mirror must re-sync the row
    assert kv.truncate(s0, 9) == 0          # idempotent at the boundary
    assert kv.bt_version[s0] == v0 + 1
    # mid-page truncation keeps the partial tail page
    assert kv.truncate(s0, 7) == 1 and len(kv.owned_pages(s0)) == 2
    # a shared page is released from this row but stays live for the
    # other reader (COW/prefix sharing during speculation)
    s1 = kv.alloc_slot()
    kv.share(s1, kv.owned_pages(s0))
    free0 = kv.free_page_count
    assert kv.truncate(s0, 4) == 1          # drops s0's 2nd page
    _check_invariants(kv)
    assert kv.free_page_count == free0      # survivor: s1 still refs it
    assert len(kv.owned_pages(s1)) == 2
    kv.release(s0)
    kv.release(s1)
    assert kv.free_page_count == kv.usable_pages


def test_compact_remaps_pages_preserving_content():
    cfg = _tiny_cfg()
    kv = PagedKVCache(cfg, n_pages=9, page_size=4, max_seqs=2,
                      max_pages_per_seq=4, dtype="float32")
    s0, s1 = kv.alloc_slot(), kv.alloc_slot()
    kv.ensure(s0, 8)
    kv.ensure(s1, 8)
    kv.release(s0)                          # leaves holes in the id space
    kv.ensure(s1, 16)

    # stamp each owned page with its (slot, index) signature
    def stamp(pool):
        for j, pid in enumerate(kv.owned_pages(s1)):
            pool = jax.tree.map(
                lambda a: a.at[:, pid].set(float(10 + j)) if a.ndim == 5 else a,
                pool)
        return pool
    kv.pool = stamp(kv.pool)

    def gather(pool):
        leaf = jax.tree.leaves(pool)[0]     # (G, P, page, Hkv, hd)
        ids = kv.block_tables[s1][:len(kv.owned_pages(s1))]
        return np.asarray(leaf[:, np.asarray(ids)])

    before = gather(kv.pool)
    kv.compact()
    _check_invariants(kv)
    after = gather(kv.pool)
    np.testing.assert_array_equal(before, after)
    # live pages now occupy the densest prefix
    assert sorted(kv.owned_pages(s1)) == list(range(1, 5))


# ---------------------------------------------------------------------------
# engine equivalence + scheduler behaviour
# ---------------------------------------------------------------------------

def test_paged_matches_dense_greedy():
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    want, _ = _run(cfg, p, _reqs(cfg, 4), batch_size=2, max_len=64)
    got, eng = _run(cfg, p, _reqs(cfg, 4), batch_size=2, max_len=64,
                    cache_kind="paged", page_size=16)
    assert got == want
    # after the run only the radix prefix index retains pages; dropping
    # it returns every page to the free list
    _check_invariants(eng.kv)
    assert eng.kv.live_pages == eng.stats["prefix_cached_pages"]
    eng._prefix.clear()
    assert eng.kv.free_page_count == eng.kv.usable_pages  # all released


def test_chunked_prefill_matches_dense_greedy():
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    want, _ = _run(cfg, p, _reqs(cfg, 3), batch_size=2, max_len=64)
    got, eng = _run(cfg, p, _reqs(cfg, 3), batch_size=2, max_len=64,
                    cache_kind="paged", page_size=16, prefill_chunk=5)
    assert got == want


def test_paged_engine_through_interpret_kernel():
    """Force the Pallas kernel (interpret mode off-TPU) for engine decode
    — the full wiring model -> kernel, not just the oracle comparison."""
    from repro.kernels import ops
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    want, _ = _run(cfg, p, _reqs(cfg, 2, max_new=4), batch_size=2,
                   max_len=48)
    ops.FORCE_PALLAS = True
    try:
        got, _ = _run(cfg, p, _reqs(cfg, 2, max_new=4), batch_size=2,
                      max_len=48, cache_kind="paged", page_size=16)
    finally:
        ops.FORCE_PALLAS = None
    assert got == want


def test_preemption_by_eviction_resumes_exactly():
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    mk = lambda: [Request(prompt=(np.arange(6) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=14)
                  for i in range(2)]
    want, _ = _run(cfg, p, mk(), batch_size=2, max_len=64)
    # pool of 4 usable pages; both sequences admitted (1 page each) but
    # together outgrow the pool mid-decode -> LIFO eviction + recompute
    got, eng = _run(cfg, p, mk(), batch_size=2, max_len=64,
                    cache_kind="paged", page_size=8, n_pages=5)
    assert eng.sched.preemptions > 0
    assert got == want


def test_paged_matches_dense_with_sliding_window():
    """Window layers can't use the rolling-buffer prefill scatter — the
    paged engine must route them through the absolute-position extend
    path. Prompt longer than the window exercises the rotation."""
    from repro.configs.base import LayerSpec
    cfg = _tiny_cfg().replace(
        pattern=(LayerSpec(kind="attn", mlp="dense", window=16),))
    p = init_params(cfg, KEY)
    mk = lambda: [Request(prompt=(np.arange(40) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=6)
                  for i in range(2)]
    want, _ = _run(cfg, p, mk(), batch_size=2, max_len=64)
    got, _ = _run(cfg, p, mk(), batch_size=2, max_len=64,
                  cache_kind="paged", page_size=16)
    assert got == want


def test_sequence_truncates_at_pool_bound_instead_of_crashing():
    """A request whose growth would outrun the whole pool truncates at
    the pool's single-sequence capacity (like dense at max_len) — it
    must not crash the run after preemption regrows its prompt."""
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    eng = ServeEngine(cfg, p, batch_size=1, max_len=32, dtype="float32",
                      cache_kind="paged", page_size=4, n_pages=5)
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=25)
    eng.run([r])
    # capacity = 4 usable pages * 4 = 16 tokens -> 4 prompt + 12 new
    assert r.done and len(r.out) == 12


def test_unservable_prompt_rejected_upfront():
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    eng = ServeEngine(cfg, p, batch_size=1, max_len=128, dtype="float32",
                      cache_kind="paged", page_size=64)   # 2 usable pages
    with pytest.raises(ValueError, match="pages"):
        eng.run([Request(prompt=np.arange(80, dtype=np.int32) % 200,
                         max_new_tokens=4)])


def test_requests_beyond_pool_capacity_all_complete():
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    reqs = _reqs(cfg, 6, max_new=4)
    done, eng = _run(cfg, p, reqs, batch_size=2, max_len=48,
                     cache_kind="paged", page_size=16, n_pages=5)
    assert all(len(r.out) == 4 and r.done for r in reqs)
    assert eng.stats["n_done"] == 6
    assert eng.stats["ttft_avg_s"] > 0 and eng.stats["tpot_avg_s"] > 0


def test_paged_sustains_more_concurrency_than_dense_budget():
    """Acceptance criterion: under the dense engine's byte budget
    (batch_size * max_len KV slots) the paged engine runs more than
    batch_size concurrent sequences, verified via page accounting."""
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    dense_slots, max_len = 2, 64
    budget_tokens = dense_slots * max_len          # 128 KV slots
    page = 16
    eng = ServeEngine(cfg, p, batch_size=4, max_len=max_len,
                      dtype="float32", cache_kind="paged", page_size=page,
                      n_pages=budget_tokens // page + 1)   # +1 null page
    reqs = [Request(prompt=(np.arange(8) + i).astype(np.int32)
                    % cfg.vocab_size, max_new_tokens=6) for i in range(4)]
    seen = []
    orig = eng._decode_tick
    eng._decode_tick = lambda: (seen.append(len(eng.sched.running)), orig())
    eng.run(reqs)
    assert all(len(r.out) == 6 for r in reqs)
    assert max(seen) > dense_slots                 # more live than dense fits
    assert eng.kv.high_water <= budget_tokens // page  # within the budget


def test_max_pages_per_seq_zero_raises():
    """0 is a configuration error (no sequence could ever hold a page),
    not a request for the default cap — the falsy-fallback regression."""
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        PagedKVCache(None, n_pages=8, page_size=4, max_seqs=2,
                     max_pages_per_seq=0, create_pool=False)


# ---------------------------------------------------------------------------
# the page pool through the layer scan
# ---------------------------------------------------------------------------

def _lane_cfg(n_layers=3):
    """tiny-lm at head_dim 128: its raw pages fill whole TPU lane tiles,
    so the decode scan carries the stacked pool and addresses pages by
    (layer, page)."""
    return _tiny_cfg().replace(n_layers=n_layers, head_dim=128)


def _scan_of(fn, *args, length):
    """The layer scan of fn's jaxpr: (carry avals, xs avals)."""
    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from eqns(inner)

    scans = [e for e in eqns(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.primitive.name == "scan" and e.params["length"] == length]
    e = max(scans, key=lambda e: len(e.invars))
    c, n = e.params["num_consts"], e.params["num_carry"]
    avals = [v.aval for v in e.invars]
    return avals[c:c + n], avals[c + n:]


def _shapes(avals):
    return {tuple(a.shape) for a in avals}


def _cache_shapes(cache, pages):
    return {tuple(a.shape) for path, a
            in jax.tree_util.tree_leaves_with_path(cache)
            if (path[-1].key in PAGE_LEAVES) == pages}


@pytest.mark.parametrize("arch,head_dim,kv_bits,carried", [
    ("tiny-lm", 128, 0, True),
    ("jamba-1.5-large-398b", 128, 0, True),
    ("tiny-lm", 64, 0, False),
    ("tiny-lm", 128, 4, False),
])
def test_decode_scan_carries_lane_dense_page_pools(arch, head_dim, kv_bits,
                                                   carried):
    """decode_step_paged: a page pool whose rows fill whole lane tiles
    sits in the layer scan's carry, not in its xs; per-slot mamba state
    (the hybrid) sits in xs. Pools with narrower rows (head_dim 64,
    binary-coded scale rows) stay in xs, a layer at a time."""
    from repro.models.model import decode_step_paged
    base = get_config(arch) if arch == "tiny-lm" else smoke_config(arch)
    cfg = base.replace(dtype="float32", remat="none", head_dim=head_dim,
                       n_layers=2 * len(base.pattern))
    params = jax.eval_shape(lambda: init_params(cfg, KEY))
    B, T, page, n_pages = 2, 3, 16, 7
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, n_pages, page, B, kv_bits=kv_bits))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    carry, xs = _scan_of(
        lambda p, c, t, s, bt: decode_step_paged(cfg, p, c, t, s, bt),
        params, cache, i32(B, 1), i32(B), i32(B, T), length=cfg.n_groups)
    pages = _cache_shapes(cache, True)
    assert pages
    if carried:
        assert pages <= _shapes(carry)
        assert not pages & _shapes(xs)
    else:
        assert pages <= _shapes(xs)
        assert not pages & _shapes(carry)
    per_slot = _cache_shapes(cache, False)
    assert per_slot <= _shapes(xs)
    assert not per_slot & _shapes(carry)
    if arch != "tiny-lm":
        assert per_slot                     # the hybrid's mamba state


def test_extend_scan_carries_the_page_pool():
    """extend_paged (chunked prefill, the prefix-hit admission) runs the
    same scan: the stacked pool rides its carry."""
    from repro.models.model import extend_paged
    cfg = _lane_cfg()
    params = jax.eval_shape(lambda: init_params(cfg, KEY))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 7, 16, 2))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    carry, xs = _scan_of(
        lambda p, c, t, s, bt, n: extend_paged(cfg, p, c, t, s, bt, n),
        params, cache, i32(1, 8), i32(1), i32(1, 3), i32(1),
        length=cfg.n_groups)
    pages = _cache_shapes(cache, True)
    assert pages <= _shapes(carry) and not pages & _shapes(xs)


def test_carried_pool_engine_matches_dense_with_sharing_and_forks():
    """A three-layer engine whose stacked pool rides the scan carry:
    prefix hits admit through extend_paged, a partial shared page is
    forked copy-on-write, and every output is token-identical to the
    dense engine. Every layer writes and reads its own (layer, page)
    slots, so a layer index off by any amount changes the tokens."""
    cfg = _lane_cfg()
    p = init_params(cfg, KEY)
    prefix = (np.arange(40, dtype=np.int32) * 3 + 5) % cfg.vocab_size
    mk = lambda: [[Request(prompt=np.concatenate(
        [prefix, (np.arange(4 + i, dtype=np.int32) * 7 + i) % 199]),
        max_new_tokens=6)] for i in range(3)]

    def serve(**kw):
        eng = ServeEngine(cfg, p, batch_size=2, max_len=96,
                          dtype="float32", **kw)
        rounds = mk()
        eng.run(rounds[0])          # seeds the prefix index
        eng.run(rounds[1] + rounds[2])
        return [r.out for rs in rounds for r in rs], eng

    want, _ = serve()
    got, eng = serve(cache_kind="paged", page_size=16)
    from repro.models.model import _carried
    assert all(_carried(c) for c in eng.cache.values())
    assert eng.stats["prefix_hits"] >= 2
    assert eng.stats["cow_forks"] >= 1
    assert got == want
