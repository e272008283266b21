"""Sharded serving stack: per-shard allocator invariants, shard-local
prefix index, the shared mesh-keyed compile cache, and — when the host
exposes >= 2 devices (CI runs this file under
XLA_FLAGS=--xla_force_host_platform_device_count=2) — sharded-vs-
single-device greedy equivalence and packed-artifact mesh loading."""
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.serve import (OutOfPages, PagedKVCache, RadixPrefixCache,
                         Request, ServeEngine)
from repro.serve import compile_cache

KEY = jax.random.PRNGKey(0)

needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=2)")


def _tiny_cfg():
    return get_config("tiny-lm").replace(dtype="float32", n_layers=2,
                                         d_model=64, d_ff=128, remat="none")


def _mesh2():
    return make_mesh((2, 1), ("data", "model"))


def _kv(n_pages=16, page_size=4, max_seqs=4, n_shards=2, **kw):
    return PagedKVCache(None, n_pages=n_pages, page_size=page_size,
                        max_seqs=max_seqs, n_shards=n_shards,
                        create_pool=False, **kw)


def _check_shard_invariants(kv):
    """The global allocator invariants, plus their per-shard versions
    and page locality (every owned page in its slot's shard)."""
    assert kv.live_pages + kv.free_page_count == kv.usable_pages
    for sh in range(kv.n_shards):
        assert kv.live_in_shard(sh) + kv.free_in_shard(sh) \
            == kv.usable_in_shard(sh)
        reserve = kv.null_page_of_shard(sh)
        assert kv.refcount(reserve) == 0
        assert reserve not in kv._free
    for s in range(kv.max_seqs):
        for pid in kv.owned_pages(s):
            assert kv.shard_of_page(pid) == kv.shard_of_slot(s)
            assert pid not in [kv.null_page_of_shard(x)
                               for x in range(kv.n_shards)]
        _check_row_in_block(kv, s)


def _check_row_in_block(kv, s):
    """Every block-table entry of slot s, unused ones included, lies in
    its shard's block: rebased onto that block as the sharded paged
    decode rebases it (kernels/ops.py:paged_decode), each id is a valid
    local page in [0, pages_per_shard)."""
    base = kv.null_page_of_shard(kv.shard_of_slot(s))
    local = kv.block_tables[s] - base
    assert ((local >= 0) & (local < kv.pages_per_shard)).all(), \
        (s, kv.block_tables[s], base)
    n = len(kv.owned_pages(s))
    assert (kv.block_tables[s, n:] == base).all()


# ---------------------------------------------------------------------------
# allocator: per-shard accounting (host-only, no devices needed)
# ---------------------------------------------------------------------------

def test_shard_geometry_and_reserve_pages():
    kv = _kv(n_pages=16, max_seqs=4, n_shards=2)
    assert kv.pages_per_shard == 8 and kv.seqs_per_shard == 2
    assert kv.usable_pages == 14 and kv.usable_in_shard(0) == 7
    assert kv.null_page_of_shard(0) == 0 and kv.null_page_of_shard(1) == 8
    assert kv.shard_of_slot(0) == 0 and kv.shard_of_slot(3) == 1
    assert kv.shard_of_page(7) == 0 and kv.shard_of_page(8) == 1
    # unsharded degenerates to the original layout
    kv1 = _kv(n_pages=9, max_seqs=3, n_shards=1)
    assert kv1.usable_pages == 8 and kv1.null_page_of_shard(0) == 0


def test_alloc_stays_in_slot_shard():
    kv = _kv()
    s0 = kv.alloc_slot(shard=0)
    s1 = kv.alloc_slot(shard=1)
    assert kv.shard_of_slot(s0) == 0 and kv.shard_of_slot(s1) == 1
    kv.ensure(s0, 10)                  # 3 pages from shard 0
    kv.ensure(s1, 6)                   # 2 pages from shard 1
    _check_shard_invariants(kv)
    assert kv.free_in_shard(0) == 4 and kv.free_in_shard(1) == 5


def test_out_of_pages_is_per_shard():
    kv = _kv(n_pages=8, page_size=4, max_seqs=2, n_shards=2,
             max_pages_per_seq=6)
    s0 = kv.alloc_slot(shard=0)
    kv.ensure(s0, 3 * 4)               # all 3 usable shard-0 pages
    with pytest.raises(OutOfPages):    # shard 1 has 3 free, irrelevant
        kv.ensure(s0, 4 * 4)
    _check_shard_invariants(kv)        # failed ensure allocated nothing
    s1 = kv.alloc_slot(shard=1)
    kv.ensure(s1, 3 * 4)               # the other shard still serves
    _check_shard_invariants(kv)


def test_cow_fork_and_release_stay_in_shard():
    kv = _kv()
    donor = kv.alloc_slot(shard=1)
    kv.ensure(donor, 8)                # 2 shard-1 pages
    fresh = kv.alloc_slot(shard=1)
    kv.share(fresh, kv.owned_pages(donor))
    copies = kv.cow_for_write(fresh, 0, 8)
    assert copies and all(kv.shard_of_page(d) == 1 for _, d in copies)
    _check_shard_invariants(kv)
    kv.release(donor)
    kv.release(fresh)
    _check_shard_invariants(kv)
    assert kv.free_in_shard(1) == kv.usable_in_shard(1)


def test_share_rejects_cross_shard_pages():
    kv = _kv()
    donor = kv.alloc_slot(shard=0)
    kv.ensure(donor, 4)
    borrower = kv.alloc_slot(shard=1)
    with pytest.raises(AssertionError, match="cross-shard"):
        kv.share(borrower, kv.owned_pages(donor))


def test_compact_remaps_within_shards():
    kv = _kv(n_pages=16, page_size=4, max_seqs=4, n_shards=2)
    slots = [kv.alloc_slot(shard=sh) for sh in (0, 1)]
    for s in slots:
        kv.ensure(s, 12)
    # free some pages to fragment, then grow again
    kv.release(slots[0])
    s0b = kv.alloc_slot(shard=0)
    kv.ensure(s0b, 8)
    kv.compact()
    _check_shard_invariants(kv)
    # compacted ids hug each shard's low range (reserve + 1 onward)
    for s in (s0b, slots[1]):
        sh = kv.shard_of_slot(s)
        lo = kv.null_page_of_shard(sh) + 1
        got = kv.owned_pages(s)
        assert got == list(range(lo, lo + len(got)))


def test_unused_block_table_entries_rebase_inside_the_shard():
    """Rows with unused trailing entries — fresh, grown, truncated,
    released — never hold an id outside their shard's block: the
    sharded decode kernel fetches a page for every entry, so an id
    below the block (the old global null page 0 on shard 1) would be
    an out-of-bounds read on the chip."""
    kv = _kv(n_pages=16, page_size=4, max_seqs=4, n_shards=2,
             max_pages_per_seq=4)
    for s in range(kv.max_seqs):      # fresh rows: all reserve
        _check_row_in_block(kv, s)
    a = kv.alloc_slot(shard=1)
    b = kv.alloc_slot(shard=1)
    kv.ensure(a, 6)                    # 2 of 4 entries used
    kv.ensure(b, 13)                   # 4 of 4
    assert kv.block_tables[a, 2] == kv.null_page_of_shard(1) != 0
    _check_shard_invariants(kv)
    kv.truncate(b, 5)                  # back to 2 pages
    _check_shard_invariants(kv)
    kv.release(a)
    _check_shard_invariants(kv)
    kv.compact()
    _check_shard_invariants(kv)


def test_pick_shard_prefers_free_pages():
    kv = _kv(n_pages=16, page_size=4, max_seqs=4, n_shards=2)
    assert kv.pick_shard() == 0        # tie -> lowest shard
    s0 = kv.alloc_slot(shard=0)
    kv.ensure(s0, 16)
    assert kv.pick_shard() == 1        # shard 0 drained
    kv.alloc_slot(shard=1)
    kv.alloc_slot(shard=1)
    assert kv.pick_shard() == 0        # shard 1 out of slots


# ---------------------------------------------------------------------------
# prefix index: shard-local chains
# ---------------------------------------------------------------------------

def test_prefix_index_is_shard_local():
    kv = _kv(n_pages=24, page_size=4, max_seqs=4, n_shards=2)
    idx = RadixPrefixCache(kv)
    s0 = kv.alloc_slot(shard=0)
    kv.ensure(s0, 8)
    toks = np.arange(8)
    idx.insert(toks, kv.owned_pages(s0))
    kv.release(s0)
    # the chain lives on shard 0: invisible to shard-1 admissions
    n, pages = idx.lookup(toks, shard=0)
    assert n == 8 and all(kv.shard_of_page(p) == 0 for p in pages)
    assert idx.lookup(toks, shard=1) == (0, [])
    assert idx.lookup(toks)[0] == 8    # unfiltered lookup still matches
    # the same prefix can be cached independently per shard
    s1 = kv.alloc_slot(shard=1)
    kv.ensure(s1, 8)
    idx.insert(toks, kv.owned_pages(s1))
    kv.release(s1)
    n1, pages1 = idx.lookup(toks, shard=1)
    assert n1 == 8 and all(kv.shard_of_page(p) == 1 for p in pages1)
    # shard-filtered eviction only drains that shard's chains
    assert idx.evict(8, shard=1) == 2
    assert idx.lookup(toks, shard=0)[0] == 8
    assert idx.lookup(toks, shard=1) == (0, [])
    _check_shard_invariants(kv)


def test_reclaim_under_pressure_is_shard_local():
    kv = _kv(n_pages=12, page_size=4, max_seqs=4, n_shards=2)
    idx = RadixPrefixCache(kv)
    for sh in (0, 1):                  # park 2 index-only pages per shard
        s = kv.alloc_slot(shard=sh)
        kv.ensure(s, 8)
        idx.insert(np.arange(8) + 100 * sh, kv.owned_pages(s))
        kv.release(s)
    assert idx.cached_pages() == 4
    # shard-0 growth pressure reclaims only shard-0 index pages
    s = kv.alloc_slot(shard=0)
    kv.ensure(s, 5 * 4)                # needs all 5 usable shard-0 pages
    assert kv.free_in_shard(1) == 3    # shard 1's cache untouched
    assert idx.lookup(np.arange(8) + 100, shard=1)[0] == 8
    _check_shard_invariants(kv)


# ---------------------------------------------------------------------------
# shared compile cache
# ---------------------------------------------------------------------------

def test_engines_share_compiled_steps():
    """Two engines with the same config borrow the SAME jitted wrappers
    from serve/compile_cache.py, and the second engine's construction
    and run add zero XLA compilations — the acceptance criterion for
    'N engines share one warmup'."""
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    mk = lambda: [Request(prompt=(np.arange(12) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=6)
                  for i in range(3)]
    kw = dict(batch_size=2, max_len=64, dtype="float32",
              cache_kind="paged", page_size=8)
    eng1 = ServeEngine(cfg, p, **kw)
    r1 = mk()
    eng1.run(r1)
    entries = compile_cache.stats()["entries"]
    sizes = {n: getattr(eng1, n)._cache_size()
             for n in ("_decode", "_prefill", "_extend", "_copy")}
    eng2 = ServeEngine(cfg, p, **kw)
    assert compile_cache.stats()["entries"] == entries
    for n in sizes:
        assert getattr(eng2, n) is getattr(eng1, n)
    r2 = mk()
    eng2.run(r2)
    assert [r.out for r in r2] == [r.out for r in r1]
    for n, before in sizes.items():
        assert getattr(eng2, n)._cache_size() == before, \
            f"{n} recompiled for an identical engine"


def test_compile_cache_keys_by_config_and_mesh():
    cfg_a = _tiny_cfg()
    cfg_b = _tiny_cfg().replace(d_ff=256)
    fa = compile_cache.get("decode_paged", cfg_a)
    assert compile_cache.get("decode_paged", cfg_a) is fa
    assert compile_cache.get("decode_paged", cfg_b) is not fa
    assert compile_cache.get("extend_paged", cfg_a) is not fa
    assert compile_cache.mesh_fingerprint(None) is None


# ---------------------------------------------------------------------------
# 2-device: equivalence + packed mesh loading (CI sharded-smoke job)
# ---------------------------------------------------------------------------

@needs2
def test_sharded_engine_matches_single_device():
    """Greedy decode over a 2-way data mesh is token-identical to the
    single-device paged engine — mixed prompt lengths, growth across
    page boundaries, more requests than slots."""
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    mk = lambda: [Request(prompt=(np.arange(10 + i % 3) * 7 + i)
                          .astype(np.int32) % cfg.vocab_size,
                          max_new_tokens=6) for i in range(5)]
    kw = dict(batch_size=2, max_len=64, dtype="float32",
              cache_kind="paged", page_size=8)
    want = mk()
    ServeEngine(cfg, p, **kw).run(want)
    mesh = _mesh2()
    eng = ServeEngine(cfg, p, mesh=mesh, **kw)
    assert eng.kv.n_shards == 2
    got = mk()
    eng.run(got)
    assert [r.out for r in got] == [r.out for r in want]
    # the pool really is partitioned: page axis split across 2 devices
    pools = [l for l in jax.tree.leaves(eng.cache)
             if l.ndim == 5 and l.shape[1] == eng.kv.n_pages]
    assert pools
    for leaf in pools:
        assert len(leaf.sharding.device_set) == 2
        assert leaf.sharding.spec[1] == "data"


@needs2
def test_sharded_engine_rejects_odd_batch():
    cfg = _tiny_cfg()
    p = init_params(cfg, KEY)
    with pytest.raises(ValueError, match="batch_size"):
        ServeEngine(cfg, p, batch_size=3, max_len=64, dtype="float32",
                    cache_kind="paged", page_size=8, mesh=_mesh2())


@needs2
def test_packed_artifact_loads_onto_mesh_and_serves(tmp_path):
    """The acceptance path: quantize -> save (v3 manifest) -> load
    directly onto a 2-way data mesh -> sharded paged serving matches the
    single-device engine token-for-token."""
    from repro.ckpt.packed import load_packed, save_packed
    from repro.core import quantize_model
    from repro.quant import QuantSpec, QuantizedTensor

    cfg = get_config("tiny-lm").replace(dtype="float32", n_layers=2)
    p = init_params(cfg, KEY)
    calib = [jax.random.randint(jax.random.fold_in(KEY, i), (2, 48), 0,
                                cfg.vocab_size) for i in range(2)]
    spec = QuantSpec.from_config(cfg.quant, method="gptqt", mode="packed")
    qp, _ = quantize_model(cfg, p, calib, spec=spec)
    save_packed(tmp_path / "m", qp, spec=spec, meta={"arch": "tiny-lm"})

    mesh = _mesh2()
    lp, _, _ = load_packed(tmp_path / "m", mesh=mesh, fsdp=True)
    # every leaf committed to the mesh; fsdp keeps K-on-data, so at
    # least the big QT codes are truly split across the two devices
    split = 0
    for leaf in jax.tree.leaves(
            lp, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        arrs = ((leaf.codes, leaf.alphas, leaf.betas)
                if isinstance(leaf, QuantizedTensor) else (leaf,))
        for a in arrs:
            assert len(a.sharding.device_set) == 2
            if a.sharding.shard_shape(a.shape) != a.shape:
                split += 1
    assert split > 0, "nothing actually sharded under fsdp=True"

    mk = lambda: [Request(prompt=(np.arange(10) * 3 + i).astype(np.int32)
                          % cfg.vocab_size, max_new_tokens=6)
                  for i in range(2)]
    kw = dict(batch_size=2, max_len=64, dtype="float32",
              cache_kind="paged", page_size=8)
    want = mk()
    ServeEngine(cfg, qp, **kw).run(want)
    got = mk()
    ServeEngine(cfg, lp, mesh=mesh, **kw).run(got)
    assert [r.out for r in got] == [r.out for r in want]


def test_reserve_page_guards_cover_every_shard():
    """share()/ref() must reject each shard's reserve page, not just
    global pid 0: shard s's reserve lives at s * pages_per_shard."""
    kv = _kv(n_pages=8, page_size=4, n_shards=2)
    reserve1 = kv.null_page_of_shard(1)
    assert reserve1 == kv.pages_per_shard and reserve1 != 0
    # a corrupt refcount on the reserve must not legitimize it — the
    # old `pid != 0` guard waved shard 1's reserve straight through
    kv._refcount[reserve1] = 1
    with pytest.raises(AssertionError):
        kv.ref(reserve1)
    s = kv.alloc_slot()
    with pytest.raises(AssertionError):
        kv.share(s, [reserve1])
    kv._refcount[reserve1] = 0
