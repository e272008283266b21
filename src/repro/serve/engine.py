"""Batched serving engine: continuous batching over prefill/decode with
two cache backends behind one switch.

  cache_kind="dense"  — the classic fixed-slot regime: `batch_size`
    sequences, each owning a dense max_len KV slab (the paper's Tab. IV
    measurement setup). Memory = B * max_len regardless of live tokens.
  cache_kind="paged"  — block-table paged KV (serve/kv_cache.py): all
    sequences share a global page pool; admission is gated on free pages
    (not slots), so short/finished sequences return their memory and the
    engine sustains more concurrency under the same byte budget. With
    prefix sharing (default on for attention-only configs) a radix index
    (serve/prefix_cache.py) maps completed prefill pages to token
    prefixes: a request with an N-token cached prefix attaches those
    pages by reference, skips N tokens of prefill, and allocates only
    its suffix pages — shared pages fork copy-on-write before any write.

Both run on the same FCFS Scheduler (serve/scheduler.py) for queueing,
admission, preemption and TTFT/TPOT metrics. Works with plain bf16/fp32
weights or GPTQT-packed QuantizedTensor params — the model dispatches
per leaf, so the engine is representation-agnostic.

Prompt lengths are padded to power-of-two buckets before the jitted
prefill (attention-only, no-window configs), so admission compiles once
per bucket instead of once per distinct prompt length.

Sharded serving: pass `mesh=` (a jax.sharding.Mesh with a "data" axis,
see launch/mesh.py:make_serve_mesh) and the engine becomes mesh-native
— the paged page pool is partitioned over the data axis (per-shard
allocator, serve/kv_cache.py), the device pool and block-table mirror
are placed with dist.sharding's cache rules, and the decode/extend
steps run under the mesh context so batch activations stay anchored to
the data axis. Model-axis tensor parallelism composes through the
params' own shardings (ckpt/packed.py:load_packed(mesh=...) places a
packed artifact straight onto the mesh). All jitted step wrappers are
borrowed from the process-wide serve/compile_cache.py, so N engines —
or N restarts of the serving loop — share one warmup per (config,
mesh).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import init_cache
from repro.serve import compile_cache
from repro.serve.kv_cache import PagedKVCache
from repro.serve.scheduler import Scheduler
from repro.serve.trace import Recorder

MIN_BUCKET = 8


def bucket_len(n: int, cap: int) -> int:
    """Smallest power-of-two >= n (floor MIN_BUCKET), clamped to cap."""
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, cap)


def pad_pow2(seq: list, fill) -> list:
    """Pad to the next power-of-two length with `fill` so jits keyed on
    the list length compile once per bucket, not once per count."""
    n = 1
    while n < len(seq):
        n *= 2
    return seq + [fill] * (n - len(seq))


@dataclass
class Request:
    prompt: np.ndarray
    max_new_tokens: int = 32
    eos: int | None = None
    out: list = field(default_factory=list)
    done: bool = False


class DenseSlotPool:
    """Slot accounting shim so the Scheduler drives the dense engine
    too: one fixed max_len 'page' per sequence, and a trivial single
    shard for the scheduler's shard protocol."""

    n_shards = 1

    def __init__(self, n_slots: int, max_len: int):
        self.max_seqs = n_slots
        self.max_len = max_len
        self._active = np.zeros((n_slots,), bool)
        self.high_water = 0
        self.usable_pages = n_slots

    def pages_for(self, n_tokens: int) -> int:
        return 1

    @property
    def free_page_count(self) -> int:
        return int((~self._active).sum())

    @property
    def used_pages(self) -> int:
        return int(self._active.sum())

    # shard protocol (one trivial shard)
    def shard_of_slot(self, slot: int) -> int:
        return 0

    def pick_shard(self):
        return 0 if self.free_page_count else None

    def free_in_shard(self, shard: int) -> int:
        return self.free_page_count

    def usable_in_shard(self, shard: int) -> int:
        return self.usable_pages

    def alloc_slot(self, shard=None):
        for i in range(self.max_seqs):
            if not self._active[i]:
                self._active[i] = True
                self.high_water = max(self.high_water, self.used_pages)
                return i
        return None

    def ensure(self, slot: int, n_tokens: int) -> None:
        assert n_tokens <= self.max_len, (n_tokens, self.max_len)

    def cow_for_write(self, slot: int, start_tok: int, end_tok: int):
        return []

    def owned_pages(self, slot: int):
        return [slot] if self._active[slot] else []

    def release(self, slot: int) -> None:
        self._active[slot] = False


class ServeEngine:
    def __init__(self, cfg, params, *, batch_size=4, max_len=512,
                 dtype=None, greedy=True, cache_kind="dense",
                 page_size=64, n_pages=None, prefill_chunk=None,
                 bucket_prompts=True, watermark=1, prefix_sharing=True,
                 prefix_max_pages=None, mesh=None, kv_bits=0,
                 kv_group_size=0, speculate=0, draft_bits=2,
                 draft_params=None, accept_rule="greedy",
                 typical_tau=0.3, state_slabs=None):
        assert cache_kind in ("dense", "paged"), cache_kind
        if kv_bits and cache_kind != "paged":
            raise ValueError(
                "kv_bits requires cache_kind='paged': the binary-coded "
                "KV layout lives in the page pool (quantize-on-write "
                "needs page-granular scatter)")
        if speculate and cache_kind != "paged":
            raise ValueError(
                "speculate requires cache_kind='paged': draft KV is "
                "written speculatively into the page pool and rejected "
                "tokens roll back by page truncation")
        if accept_rule not in ("greedy", "typical"):
            raise ValueError(
                f"accept_rule={accept_rule!r}; expected 'greedy' or "
                f"'typical'")
        self.cfg = cfg
        self.params = params
        self.B = batch_size
        self.max_len = max_len
        self.greedy = greedy
        self.cache_kind = cache_kind
        self.kv_bits = int(kv_bits)
        self.mesh = mesh
        # pool shards = the mesh's data-axis size: page blocks land on
        # the same devices as the batch rows whose sequences use them
        data_shards = 1
        if mesh is not None:
            from repro.dist.sharding import mesh_axis_sizes
            data_shards = int(mesh_axis_sizes(mesh).get("data", 1))
        n_shards = 1
        if cache_kind == "paged" and data_shards > 1:
            if batch_size % data_shards:
                raise ValueError(
                    f"batch_size={batch_size} must divide over the "
                    f"{data_shards}-way data axis so every sequence "
                    f"slot maps to exactly one page-pool shard")
            n_shards = data_shards
        dtype = dtype or cfg.dtype

        # MLA counts as attention here: its latent pages ride the same
        # block-table/COW/prefix machinery, and its extend path exists
        # (models/mla.py:mla_extend_paged)
        attn_only = all(s.kind == "attn" for s in cfg.pattern)
        no_window = all(s.window is None for s in cfg.pattern)
        if speculate and (not attn_only or cfg.mla is not None):
            raise NotImplementedError(
                "speculate>0 verifies k+1 positions through the paged "
                "extend path, which needs a standard attention-only "
                "pattern (MLA drafts are not wired up)")
        # bucketed prefill needs padding tokens to be harmless: causal
        # attention masks them and decode overwrites their cache slots,
        # but rolling window buffers and recurrent mamba state both mix
        # pad tokens in — keep those configs on exact-length prefill.
        self._bucket = bool(bucket_prompts and attn_only and no_window)

        # window layers: prefill()'s rolling buffer cannot be scattered
        # into absolute page slots, so the paged engine prefills them
        # through the extend path (which is attention-only)
        self._extend_prefill = cache_kind == "paged" and \
            (bool(prefill_chunk) or not no_window)
        self._prefix = None
        self.slab = None
        if cache_kind == "paged":
            if self._extend_prefill and not attn_only:
                raise NotImplementedError(
                    "paged prefill via extend (chunked or sliding-window) "
                    "needs an attention-only pattern")
            pages_per_seq = -(-max_len // page_size)
            if n_pages is None:
                # parity with the dense engine's byte budget, + one
                # reserve (null) page per shard
                n_pages = batch_size * pages_per_seq + n_shards
            # the page axis must split evenly over the shards (it is
            # the GSPMD-partitioned dim of the pool)
            n_pages = -(-n_pages // n_shards) * n_shards
            self.kv = PagedKVCache(cfg, n_pages=n_pages,
                                   page_size=page_size,
                                   max_seqs=batch_size,
                                   max_pages_per_seq=pages_per_seq,
                                   dtype=dtype, n_shards=n_shards,
                                   kv_bits=kv_bits,
                                   kv_group_size=kv_group_size)
            self.page_size = page_size
            # recurrent layers: pooled fixed-size state slabs under the
            # page-pool's allocator invariants — admission claims one
            # slab per sequence, exhaustion is declined like OutOfPages
            if not attn_only:
                from repro.serve.state_slab import StateSlabPool
                n_slabs = (batch_size + n_shards if state_slabs is None
                           else int(state_slabs))
                n_slabs = -(-n_slabs // n_shards) * n_shards
                self.slab = StateSlabPool(cfg, n_slabs=n_slabs,
                                          max_seqs=batch_size,
                                          n_shards=n_shards, dtype=dtype)
            # prefix sharing skips matched prefill via the extend path,
            # so it has the same attention-only requirement
            if prefix_sharing and attn_only:
                from repro.serve.prefix_cache import RadixPrefixCache
                self._prefix = RadixPrefixCache(
                    self.kv, max_cached_pages=prefix_max_pages)
            self.cache = self.kv.take_pool()
            # device-resident block-table mirror: rows are pushed only
            # when the allocator bumps their version (admission, growth,
            # COW, release) instead of re-uploading the whole table per
            # decode tick; the per-tick traffic is just the (B,) live
            # mask that routes inactive rows to their shard's null page
            self._bt_dev = jnp.asarray(self.kv.block_tables)
            self._bt_applied = np.full((batch_size,), -1, np.int64)
            # per-slot null-page row: all zeros unsharded; shard s's
            # reserve page for slots living on shard s
            self._null_row = jnp.asarray(
                [self.kv.null_page_of_slot(s) for s in range(batch_size)],
                jnp.int32)
            self._bt_update = compile_cache.get("bt_update", None, mesh)
            self._decode = compile_cache.get("decode_paged", cfg, mesh)
            self._scatter = compile_cache.get("scatter_prefill", cfg,
                                              mesh)
            self._extend = compile_cache.get("extend_paged", cfg, mesh)
            self._copy = compile_cache.get("copy_pages", None, mesh)
            if speculate:
                self._draft_propose = compile_cache.get("draft_propose",
                                                        cfg, mesh)
                self._verify = compile_cache.get("verify_paged", cfg,
                                                 mesh)
        else:
            if prefill_chunk:
                raise NotImplementedError(
                    "chunked prefill requires cache_kind='paged'")
            self.kv = DenseSlotPool(batch_size, max_len)
            self.cache = init_cache(cfg, batch_size, max_len, dtype)
            self._decode = compile_cache.get("decode_dense", cfg, mesh)
        if mesh is not None:
            # place the cache (page pools / dense slabs, block-table
            # mirror) onto the mesh with the shared GSPMD cache rules:
            # pages and batch rows ride the data axis, KV heads the
            # model axis when divisible
            from repro.dist.sharding import batch_pspec, cache_shardings
            from jax.sharding import NamedSharding
            self.cache = jax.device_put(
                self.cache, cache_shardings(cfg, self.cache, mesh))
            if cache_kind == "paged":
                row = NamedSharding(mesh, batch_pspec(mesh, batch_size))
                self._bt_dev = jax.device_put(self._bt_dev, row)
                self._null_row = jax.device_put(
                    self._null_row,
                    NamedSharding(mesh, batch_pspec(mesh, batch_size,
                                                    ())))

        self.prefill_chunk = prefill_chunk
        # raw accumulators (hot path); `stats_snapshot()` freezes them
        # plus the pool/index/compile-cache counters into an EngineStats.
        # The recorder's spans and counters (serve/trace.py) land here
        # too, as flat `<span>_s` / `<span>_n` and counter keys;
        # `prefill_s` and `decode_s` are the totals of the spans that
        # feed them.
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0, "tokens": 0,
                      "ticks": 0, "prefill_tokens": 0,
                      "draft_tokens": 0, "accepted_tokens": 0}
        self.recorder = Recorder(self.stats)
        self.sched = Scheduler(
            self.kv, watermark=watermark if cache_kind == "paged" else 0,
            prefill_chunk=prefill_chunk, prefix=self._prefix,
            slab=self.slab, recorder=self.recorder)
        self.pos = np.zeros((batch_size,), np.int32)
        self.cur = np.zeros((batch_size,), np.int32)
        self._prefill = compile_cache.get("prefill", cfg, mesh)
        # self-speculative decoding: the draft shares the target's
        # packed sign words and differs only in its (re-fit) scales —
        # zero extra HBM beyond the draft alphas/betas (quant/draft.py)
        self.speculate = int(speculate)
        self.draft_bits = int(draft_bits)
        self.accept_rule = accept_rule
        self.typical_tau = float(typical_tau)
        self.draft_params = None
        if self.speculate:
            if draft_params is None:
                from repro.quant.draft import make_draft_params
                from repro.quant.qlinear import QuantizedTensor
                has_qt = any(
                    isinstance(leaf, QuantizedTensor)
                    for leaf in jax.tree.leaves(
                        params,
                        is_leaf=lambda x: isinstance(x, QuantizedTensor)))
                if not has_qt:
                    raise ValueError(
                        "speculate>0 needs GPTQT-quantized params (the "
                        "draft is a code-plane prefix of the target) or "
                        "an explicit draft_params tree")
                draft_params = make_draft_params(params, self.draft_bits)
            self.draft_params = draft_params
        self._entries = []

    def stats_snapshot(self):
        """Structured snapshot of every serving counter — engine
        accumulators, scheduler request metrics (incl. per-request
        TTFT/TPOT samples), page-pool/prefix-index counters and the
        process-wide compile-cache stats — as an immutable EngineStats.
        This is the export the bench scenarios record; the `stats` dict
        stays the mutable in-flight accumulator."""
        from repro.serve.stats import EngineStats
        return EngineStats.capture(self)

    def _mesh_ctx(self):
        """The engine's mesh context (no-op single-device): every jitted
        step is traced inside it so constrain_batch anchors activations
        to the data axis."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.dist.context import mesh_context
        return mesh_context(self.mesh)

    # ---------------- COW fork application ----------------
    def _apply_copies(self, copies) -> None:
        """Apply allocator COW forks to the device pool. The copy list
        is padded with (0, 0) null-page no-ops to a power-of-two length
        so the jit compiles once per bucket, not once per fork count."""
        if not copies:
            return
        padded = pad_pow2(copies, (0, 0))
        src = [s for s, _ in padded]
        dst = [d for _, d in padded]
        self.cache = self._copy(self.cache,
                                jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32),
                                self.kv.n_pages)

    # ---------------- device block-table mirror ----------------
    def _sync_block_tables(self) -> None:
        """Push block-table rows whose allocator version moved since the
        last sync. The row-index list is padded to a power-of-two length
        (repeating the last row — an idempotent rewrite) so the scatter
        jit compiles once per bucket, not once per dirty count."""
        dirty = [s for s in range(self.B)
                 if self._bt_applied[s] != self.kv.bt_version[s]]
        if not dirty:
            return
        self.recorder.count("bt_rows_synced", len(dirty))
        idx = pad_pow2(dirty, dirty[-1])
        rows = self.kv.block_tables[idx]
        self._bt_dev = self._bt_update(self._bt_dev,
                                       jnp.asarray(idx, jnp.int32),
                                       jnp.asarray(rows, jnp.int32))
        for s in dirty:
            self._bt_applied[s] = self.kv.bt_version[s]

    # ---------------- admission ----------------
    def _padded_prompt(self, prompt):
        L = len(prompt)
        S = bucket_len(L, self.max_len) if self._bucket else L
        padded = np.zeros((S,), np.int32)
        padded[:L] = prompt
        return padded, L

    def _admit(self, e):
        span = self.recorder.span
        with span("serve.admit", e.rid, feeds="prefill_s"):
            if e.shared_tokens:
                # attach the matched prefix pages by reference BEFORE any
                # allocation: the attach pins them (refcount >= 2) against
                # the allocator's index reclaim
                self.kv.share(e.slot, e.shared_pages)
                e.prefilled = e.shared_tokens
            if self.prefill_chunk:
                # chunked mode: admission only reserves the slot (plus any
                # shared prefix); prompt tokens flow through _prefill_tick
                # one chunk per engine tick
                self.pos[e.slot] = 0
                return
            L = len(e.prompt)
            if e.shared_tokens:
                # prefix hit: prefill only the unshared suffix through the
                # extend path; the COW fork (if the match ends mid-page)
                # happens before the suffix K/V lands in pages
                N = e.shared_tokens
                suffix = e.prompt[N:]
                nv = len(suffix)
                C = bucket_len(nv, self.max_len) if self._bucket else nv
                padded = np.zeros((C,), np.int32)
                padded[:nv] = suffix
                with span("serve.admit.cow"):
                    self.kv.ensure(e.slot, L)
                    self._apply_copies(self.kv.cow_for_write(e.slot, N, L))
                with span("serve.admit.forward", nv):
                    bt = self._bt_slice(e.slot, L)
                    logits, self.cache = self._extend(
                        self.params, self.cache,
                        jnp.asarray(padded[None], jnp.int32),
                        jnp.asarray([N], jnp.int32), bt,
                        jnp.asarray([nv], jnp.int32))
                self.stats["prefill_tokens"] += nv
                self._emit_first_token(e, logits, L)
                return
            padded, L = self._padded_prompt(e.prompt)
            self.stats["prefill_tokens"] += L
            if self.cache_kind == "paged":
                with span("serve.admit.cow"):
                    self.kv.ensure(e.slot, L)
            with span("serve.admit.forward", L):
                tokens = jnp.asarray(padded[None, :], jnp.int32)
                last = jnp.asarray([L - 1], jnp.int32)
                if self._extend_prefill:
                    # sliding-window layers: write the prompt at absolute
                    # page slots via one whole-prompt extend step
                    bt = self._bt_slice(e.slot, L)
                    last_logits, self.cache = self._extend(
                        self.params, self.cache, tokens,
                        jnp.asarray([0], jnp.int32), bt,
                        jnp.asarray([L], jnp.int32))
                elif self.cache_kind == "paged":
                    last_logits, row_cache = self._prefill(
                        self.params, tokens, last, len(padded))
                    npg = -(-len(padded) // self.page_size)
                    ids = self.kv.owned_pages(e.slot)
                    # reserve-page pad (masked out), inside the slot's shard
                    ids = (ids + [self.kv.null_page_of_slot(e.slot)]
                           * npg)[:npg]
                    self.cache = self._scatter(self.cache, row_cache,
                                               jnp.int32(e.slot),
                                               jnp.asarray(ids, jnp.int32),
                                               jnp.int32(L))
                else:
                    last_logits, cache1 = self._prefill(
                        self.params, tokens, last, self.max_len)
                    slot = e.slot

                    def merge(batch_leaf, one_leaf):
                        # leaves: (G, B, ...) vs (G, 1, ...)
                        return batch_leaf.at[:, slot].set(one_leaf[:, 0])
                    self.cache = jax.tree.map(merge, self.cache, cache1)
            self._emit_first_token(e, last_logits, L)

    def _emit_first_token(self, e, last_logits, prompt_len):
        with self.recorder.span("serve.admit.first_token", e.rid):
            tok = int(jnp.argmax(last_logits[0]))
            e.req.out.append(tok)
            if not e.metrics.t_first_token:
                e.metrics.t_first_token = time.time()
            self.pos[e.slot] = prompt_len
            self.cur[e.slot] = tok
            e.prefilled = prompt_len
            if self._prefix is not None:
                # index the prompt's full pages right away so concurrent
                # same-prefix requests share them; these pages are never
                # written again (decode lands at positions >= prompt_len).
                # The partial tail page is indexed at finish() instead —
                # indexing it now would force a COW fork on the very next
                # decode token.
                nfull = prompt_len // self.page_size
                if nfull:
                    self._prefix.insert(
                        np.asarray(e.prompt[:nfull * self.page_size]),
                        self.kv.owned_pages(e.slot)[:nfull])
            # the prefill-produced token can already satisfy the request
            if (len(e.req.out) >= e.req.max_new_tokens
                    or (e.req.eos is not None and tok == e.req.eos)):
                self._finish(e)

    def _finish(self, e):
        """Complete a request, handing the tokens whose KV its pages
        hold (prompt + generated-minus-last) to the scheduler so the
        radix index can retain them for future prefix hits."""
        slot = e.slot
        if self._prefix is None:
            self.sched.finish(slot)
            return
        n_cached = int(self.pos[slot])
        folded = len(e.prompt) - e.metrics.n_prompt   # resumed prompts
        toks = np.concatenate([
            e.prompt, np.asarray(e.req.out[folded:], np.int32)])[:n_cached]
        self.sched.finish(slot, cached_tokens=toks)

    def _bt_slice(self, slot, n_tokens):
        """Block-table row cut to the pages covering n_tokens, so the
        extend gather is O(live tokens) — not O(max_len) — per chunk.
        The jit retraces per distinct page count (bounded by
        max_pages_per_seq)."""
        npg = self.kv.pages_for(n_tokens)
        return jnp.asarray(self.kv.block_tables[slot:slot + 1, :npg])

    # ---------------- chunked prefill ----------------
    def _prefill_tick(self):
        """Advance the oldest admitted-but-unprefilled sequence by one
        chunk; long prompts therefore never stall decode ticks. With a
        prefix hit, chunking starts at the matched offset (prefilled
        was set to shared_tokens at admission)."""
        pending = [e for e in self.sched.running.values()
                   if e.prefilled < len(e.prompt)]
        if not pending:
            return
        e = min(pending, key=lambda x: x.metrics.t_admit)
        C = self.prefill_chunk
        s = e.prefilled
        chunk = e.prompt[s:s + C]
        nv = len(chunk)
        ok, copies = self.sched.ensure_write_capacity(e.slot, s, s + nv)
        if not ok:
            return    # evicted while growing; it will be re-admitted
        with self.recorder.span("serve.prefill_chunk", e.rid,
                                feeds="prefill_s"):
            padded = np.zeros((C,), np.int32)
            padded[:nv] = chunk
            self._apply_copies(copies)
            bt = self._bt_slice(e.slot, s + nv)
            logits, self.cache = self._extend(
                self.params, self.cache,
                jnp.asarray(padded[None], jnp.int32),
                jnp.asarray([s], jnp.int32), bt,
                jnp.asarray([nv], jnp.int32))
            e.prefilled = s + nv
            self.stats["prefill_tokens"] += nv
            if e.prefilled >= len(e.prompt):
                self._emit_first_token(e, logits, len(e.prompt))

    # ---------------- decode ----------------
    def _decode_ready(self):
        return [s for s, e in self.sched.running.items()
                if e.prefilled >= len(e.prompt)]

    def _decode_tick(self):
        if self.speculate:
            return self._spec_decode_tick()
        ready = self._decode_ready()
        if not ready:
            return
        span = self.recorder.span
        with span("serve.decode.tick", len(ready)):
            if self.cache_kind == "paged":
                with span("serve.decode.grow"):
                    grown = []
                    for slot in ready:
                        if slot not in self.sched.running:
                            continue    # evicted while growing an earlier slot
                        # the new token lands at pos -> need pos+1 capacity,
                        # and a COW fork if that page is shared (its forks
                        # must hit the device pool before this slot is
                        # marked ready)
                        p = int(self.pos[slot])
                        ok, copies = self.sched.ensure_write_capacity(
                            slot, p, p + 1)
                        if ok:
                            self._apply_copies(copies)
                            grown.append(slot)
                    # a later growth may have evicted an earlier grown slot
                    ready = [s for s in grown if s in self.sched.running]
                if not ready:
                    return
            self.recorder.count("decode_rows", len(ready))
            with span("serve.decode.step", len(ready), feeds="decode_s"):
                if self.cache_kind == "paged":
                    with span("serve.decode.bt_sync"):
                        self._sync_block_tables()
                with span("serve.decode.dispatch"):
                    toks = jnp.asarray(self.cur[:, None], jnp.int32)
                    pos = jnp.asarray(self.pos, jnp.int32)
                    if self.cache_kind == "paged":
                        live = np.zeros((self.B,), np.int32)
                        live[ready] = 1   # masked rows write to the null page
                        logits, self.cache = self._decode(
                            self.params, self.cache, toks, pos,
                            self._bt_dev, jnp.asarray(live), self._null_row)
                    else:
                        logits, self.cache = self._decode(
                            self.params, self.cache, toks, pos)
                with span("serve.decode.wait"):
                    logits.block_until_ready()
            self.stats["ticks"] += 1
            with span("serve.decode.readback"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with span("serve.decode.emit"):
                for slot in ready:
                    e = self.sched.running[slot]
                    self.stats["tokens"] += 1
                    tok = int(nxt[slot])
                    e.req.out.append(tok)
                    self.pos[slot] += 1
                    self.cur[slot] = tok
                    hit_eos = e.req.eos is not None and tok == e.req.eos
                    if (len(e.req.out) >= e.req.max_new_tokens or hit_eos
                            or self.pos[slot] >= self._seq_cap() - 1):
                        self._finish(e)

    # ---------------- speculative decode ----------------
    def _spec_decode_tick(self):
        """Propose -> verify -> accept. The draft proposes up to k
        tokens per ready sequence (k draft decode steps; draft KV lands
        speculatively at pos..pos+k-1), then ONE batched target pass
        scores the k+1 positions [cur, draft...] with causal masking —
        and, crucially, overwrites every speculatively-written K/V slot
        with the target's own K/V, which is what makes greedy
        speculative decode token-identical to target-only decode for
        ANY draft. Acceptance takes the longest draft prefix the target
        agrees with plus the target's token at the first disagreement
        (or the bonus token after full acceptance); rejected tokens
        roll back by truncating pos and unref'ing whole pages past the
        accept point (kv.truncate) — stale K/V inside the kept tail
        page is masked by context length and overwritten by the next
        write, exactly like any partial tail page."""
        k = self.speculate
        cap = self._seq_cap()
        ready = self._decode_ready()
        if not ready:
            return
        span = self.recorder.span
        with span("serve.decode.tick", len(ready)):
            k_eff = {}
            with span("serve.decode.grow"):
                grown = []
                for slot in ready:
                    if slot not in self.sched.running:
                        continue    # evicted while growing an earlier slot
                    p = int(self.pos[slot])
                    # clamp speculation depth at the sequence capacity: the
                    # verify pass writes k_eff+1 positions starting at pos
                    ke = min(k, cap - 1 - p)
                    ok, copies = self.sched.ensure_write_capacity(
                        slot, p, p + ke + 1)
                    if ok:
                        self._apply_copies(copies)
                        k_eff[slot] = ke
                        grown.append(slot)
                ready = [s for s in grown if s in self.sched.running]
            if not ready:
                return
            self.recorder.count("decode_rows", len(ready))
            with span("serve.decode.step", len(ready), feeds="decode_s"):
                self._sync_block_tables()
                base_pos = self.pos.copy()

                # ---- propose: ONE fused k-step draft pass (on-device
                # argmax feedback loop, models/model.py:draft_propose_paged)
                # instead of k host round-trips — the per-step dispatch +
                # transfer overhead used to dominate the tick and cancel
                # the speculation gain. Rows whose clamped depth is
                # exhausted (k_eff <= j) write to their shard's null page
                # at position 0, like any inactive row.
                ke_arr = np.zeros((self.B,), np.int32)
                for s in ready:
                    ke_arr[s] = k_eff[s]
                dt_dev, self.cache = self._draft_propose(
                    self.draft_params, self.cache,
                    jnp.asarray(self.cur, jnp.int32),
                    jnp.asarray(base_pos, jnp.int32), self._bt_dev,
                    jnp.asarray(ke_arr), self._null_row, k)

                # ---- verify: one batched target pass over k+1
                # positions; the verify tokens are assembled on device so
                # draft tokens never round-trip through the host before
                # verify is dispatched
                verify_toks = jnp.concatenate(
                    [jnp.asarray(self.cur[:, None], jnp.int32), dt_dev],
                    axis=1)
                live = np.zeros((self.B,), np.int32)
                live[ready] = 1
                n_valid = np.zeros((self.B,), np.int32)
                for s in ready:
                    n_valid[s] = k_eff[s] + 1
                logits_all, self.cache = self._verify(
                    self.params, self.cache, verify_toks,
                    jnp.asarray(np.where(live > 0, base_pos, 0), jnp.int32),
                    self._bt_dev, jnp.asarray(n_valid), jnp.asarray(live),
                    self._null_row)
                draft_toks = np.asarray(dt_dev)                  # (B, k)
                greedy = np.asarray(
                    jnp.argmax(logits_all, axis=-1))             # (B, k+1)
                probs = (np.asarray(jax.nn.softmax(logits_all, axis=-1))
                         if self.accept_rule == "typical" else None)
            self.stats["ticks"] += 1

            # ---- accept
            with span("serve.decode.emit"):
                for slot in ready:
                    self._accept(slot, k_eff[slot], draft_toks[slot],
                                 greedy[slot], probs, int(base_pos[slot]),
                                 cap)

    def _accept(self, slot, ke, dt, g, probs, base, cap) -> None:
        """Emit the accepted draft prefix of one row plus the target's
        token after it, and roll the row's pages back to what it kept."""
        e = self.sched.running[slot]
        self.stats["draft_tokens"] += ke
        m = 0
        if probs is not None:
            # typical acceptance: keep a draft token the target
            # gives at least typical_tau of its own argmax mass
            while m < ke:
                pm = probs[slot, m]
                if pm[dt[m]] < self.typical_tau * pm.max():
                    break
                m += 1
        else:
            while m < ke and dt[m] == g[m]:
                m += 1
        self.stats["accepted_tokens"] += m
        # accepted draft prefix + the target's token at position m
        # (correction on mismatch, bonus after full acceptance) —
        # emitted one by one under the vanilla stop conditions
        burst = [int(dt[j]) for j in range(m)] + [int(g[m])]
        emitted, fin = 0, False
        for tok in burst:
            e.req.out.append(tok)
            self.stats["tokens"] += 1
            emitted += 1
            hit_eos = e.req.eos is not None and tok == e.req.eos
            if (len(e.req.out) >= e.req.max_new_tokens or hit_eos
                    or base + emitted >= cap - 1):
                fin = True
                break
        new_pos = base + emitted
        self.pos[slot] = new_pos
        self.cur[slot] = burst[emitted - 1]
        # rollback: KV is cached for [0, new_pos); whole pages past
        # that point return to the pool (or to their other readers)
        self.kv.truncate(slot, new_pos)
        if fin:
            self._finish(e)

    # ---------------- engine ----------------
    def _seq_cap(self) -> int:
        """Per-sequence token capacity: max_len, further bounded by what
        one page-pool shard can ever hold for one sequence — sequences
        truncate here (like dense at max_len) instead of outgrowing the
        pool (a sequence's pages all come from its slot's shard)."""
        if self.cache_kind == "dense":
            return self.max_len
        return min(self.max_len,
                   self.kv.usable_in_shard(0) * self.page_size)

    def run(self, requests: list[Request]):
        cap = self._seq_cap()
        # validate the whole batch BEFORE submitting anything: a rejected
        # request must not leave earlier ones queued in the scheduler
        for r in requests:
            if len(r.prompt) >= cap:
                raise ValueError(
                    f"prompt of {len(r.prompt)} tokens cannot fit the "
                    f"engine capacity of {cap} tokens")
            if self.cache_kind == "paged":
                # same arithmetic as the admission gate (with sharing
                # counted as zero — it is best-effort), so an unservable
                # request is rejected here instead of crashing mid-run
                need = self.sched.admission_need(len(r.prompt))
                if need > self.kv.usable_in_shard(0):
                    raise ValueError(
                        f"prompt of {len(r.prompt)} tokens needs {need} "
                        f"pages (incl. watermark) but a pool shard only "
                        f"has {self.kv.usable_in_shard(0)}")
        for r in requests:
            self.sched.submit(r)
        self._entries = list(self.sched.waiting)
        with self._mesh_ctx():
            while self.sched.has_work():
                while True:
                    e = self.sched.try_admit()
                    if e is None:
                        break
                    self._admit(e)
                if self.cache_kind == "paged" and self.prefill_chunk:
                    self._prefill_tick()
                self._decode_tick()
        self.stats.update(self.sched.metrics_summary(self._entries))
        return requests
