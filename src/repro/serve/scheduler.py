"""Continuous-batching scheduler: FCFS admission gated on free KV pages,
prefix-sharing-aware accounting, chunked prefill, preemption-by-eviction,
and per-request metrics.

The scheduler owns the queue/lifecycle policy and the page accounting;
the engine owns the model calls. Separation matters: every later scaling
PR (sharded serving, multi-host routing) swaps the engine's model calls
while reusing this policy layer unchanged.

Policies (see docs/SERVING.md):
  - admission: FCFS. A request is admitted when a sequence slot is free
    AND the pool can hold its prompt pages plus `watermark` spare pages
    (the spare keeps one decode tick's growth from immediately starving).
    With a prefix index attached, admission charges only the *unshared*
    suffix of the prompt: matched pages are attached by reference, plus
    one fork page when the match ends mid-page (copy-on-write).
  - prefill: optionally chunked — at most one chunk of one admitted
    request is processed per engine tick, so a long prompt cannot stall
    the decode ticks of already-running sequences.
  - preemption: when decode growth runs out of pages, the allocator
    first reclaims unreferenced prefix-index pages; only then is the
    *youngest* active sequence (LIFO) evicted — its references are
    dropped and the request re-queued at the queue front with prompt :=
    prompt + tokens generated so far (recompute-on-resume, the classic
    vLLM recovery). Greedy decoding makes the recomputation exact, and
    index-retained prefix pages make it cheap: the resumed prompt
    usually re-matches its own pages.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from repro.serve.kv_cache import OutOfPages, PagedKVCache
from repro.serve.trace import Recorder


@dataclass
class RequestMetrics:
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    n_prompt: int = 0
    n_generated: int = 0
    n_preemptions: int = 0
    n_prefix_tokens: int = 0          # prompt tokens served from the index

    @property
    def ttft_s(self) -> float:
        return (self.t_first_token - self.t_submit) if self.t_first_token else 0.0

    @property
    def tpot_s(self) -> float:
        """Time per output token after the first."""
        if self.n_generated <= 1 or not self.t_done:
            return 0.0
        return (self.t_done - self.t_first_token) / (self.n_generated - 1)


@dataclass
class _Entry:
    req: object                       # engine Request
    prompt: np.ndarray                # current (possibly extended) prompt
    metrics: RequestMetrics = field(default_factory=RequestMetrics)
    rid: int = -1                     # request id, assigned at submit
    slot: int = -1
    prefilled: int = 0                # prompt tokens already in pages
    shared_tokens: int = 0            # prefix tokens matched at admission
    shared_pages: list = field(default_factory=list)


class Scheduler:
    """FCFS continuous batching over a PagedKVCache."""

    def __init__(self, kv: PagedKVCache, *, watermark: int = 1,
                 prefill_chunk: int | None = None, prefix=None,
                 slab=None, recorder: Recorder | None = None):
        self.kv = kv
        self.watermark = int(watermark)
        self.prefill_chunk = prefill_chunk
        self.prefix = prefix              # RadixPrefixCache or None
        # StateSlabPool (serve/state_slab.py) for recurrent-state
        # configs: admission additionally claims one fixed-size state
        # slab, and slab exhaustion is declined/preempted exactly like
        # page exhaustion (same OutOfPages)
        self.slab = slab
        self.waiting: deque[_Entry] = deque()
        self.running: dict[int, _Entry] = {}   # slot -> entry
        self.preemptions = 0
        # spans and counters (serve/trace.py); the engine passes its own
        self.recorder = recorder if recorder is not None else Recorder()
        self._next_rid = 0

    # ---------------- queue ----------------
    def submit(self, req) -> None:
        e = _Entry(req=req, prompt=np.asarray(req.prompt, np.int32),
                   rid=self._next_rid)
        self._next_rid += 1
        e.metrics.t_submit = time.time()
        e.metrics.n_prompt = len(e.prompt)
        self.waiting.append(e)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ---------------- admission ----------------
    def admission_need(self, prompt_len: int, *, resumed: bool = False,
                       shared_tokens: int = 0) -> int:
        """Free pages required to admit a prompt: its pages + one decode
        token + the watermark, minus pages covering the shared prefix
        (attached by reference, not allocated), plus one fork page when
        the match ends mid-page (the borrower COW-forks that page before
        writing its suffix into it). Resumed (preempted) entries skip
        the watermark: their grown prompt is already bounded by the
        engine's capacity truncation, and they must get back in to
        finish. The engine's run()-time validation uses the same
        arithmetic with shared_tokens=0 (sharing is best-effort)."""
        wm = 0 if resumed else self.watermark
        need = self.kv.pages_for(prompt_len + 1) + wm
        if shared_tokens:
            need -= self.kv.pages_for(shared_tokens)
            if shared_tokens % self.kv.page_size:
                need += 1
        return need

    # thin shard adapters: PagedKVCache and DenseSlotPool both expose
    # the shard protocol (DenseSlotPool is trivially one shard)
    def _pick_shard(self):
        return self.kv.pick_shard()

    def _alloc_slot(self, shard):
        return self.kv.alloc_slot(shard=shard)

    def _free_in_shard(self, shard):
        return self.kv.free_in_shard(shard)

    def _usable_in_shard(self, shard):
        return self.kv.usable_in_shard(shard)

    def try_admit(self) -> _Entry | None:
        """Admit the queue head if a slot + its unshared prompt pages
        fit, reclaiming index-only pages when that is what stands in the
        way. The prefix match is re-run after every reclaim round: an
        eviction may have dropped pages the previous lookup matched.
        Over a sharded pool the target shard is chosen first (the one
        with the most free pages among shards with a free slot), and
        the prefix match, the page accounting and the reclaim all run
        against that shard alone — the admitted sequence's pages must
        come from the shard its slot lives on."""
        if not self.waiting:
            return None
        # no free sequence slot -> nothing to admit; bail before the
        # reclaim loop below so a full batch doesn't drain cached
        # prefixes that couldn't have helped anyway
        if len(self.running) >= self.kv.max_seqs:
            return None
        e = self.waiting[0]
        with self.recorder.span("serve.sched.admit", e.rid):
            return self._admit_head(e)

    def _admit_head(self, e: _Entry) -> _Entry | None:
        """try_admit's body for the queue head `e`, once a sequence
        slot is known to be free."""
        resumed = e.metrics.n_preemptions > 0
        shard = self._pick_shard()
        if shard is None:
            return None
        free_pages = lambda: self._free_in_shard(shard)
        # one admission == one lookup in the hit-rate stats, however
        # many reclaim rounds re-run the match (count=False retries keep
        # the result fresh without inflating lookups / node hit counters)
        count = True
        while True:
            shared_tokens, shared_pages = 0, []
            if self.prefix is not None and len(e.prompt) > 1:
                shared_tokens, shared_pages = self.prefix.lookup(
                    e.prompt, max_tokens=len(e.prompt) - 1, shard=shard,
                    count=count)
                count = False
            need = self.admission_need(len(e.prompt), resumed=resumed,
                                       shared_tokens=shared_tokens)
            if need > self._usable_in_shard(shard):
                raise ValueError(
                    f"request needs {need} pages but a pool shard only "
                    f"has {self._usable_in_shard(shard)}; it can never "
                    f"be admitted")
            if need <= free_pages():
                break
            shortfall = need - free_pages()
            if (self.prefix is None
                    or self.prefix.evict(shortfall, shard=shard) == 0):
                return None
        slot = self._alloc_slot(shard)
        if slot is None:
            return None
        if self.slab is not None:
            try:
                self.slab.alloc(slot)
            except OutOfPages:
                # state-slab exhaustion == page exhaustion: give the
                # slot back (nothing was allocated) and wait for a
                # running sequence to return its slab
                self.kv.release(slot)
                return None
        self.waiting.popleft()
        e.slot = slot
        e.prefilled = 0
        e.shared_tokens = shared_tokens
        e.shared_pages = list(shared_pages)
        if shared_tokens and self.prefix is not None:
            self.prefix.hits += 1
            self.prefix.tokens_saved += shared_tokens
            e.metrics.n_prefix_tokens += shared_tokens
        e.metrics.t_admit = time.time()
        self.running[slot] = e
        rec = self.recorder
        rec.count("admit_prompt_tokens", len(e.prompt))
        rec.count("admit_prefix_tokens", shared_tokens)
        return e

    # ---------------- preemption ----------------
    def _preempt_slot(self, slot: int) -> _Entry:
        """Evict one running sequence: drop its page references, requeue
        it at the queue front with prompt := prompt + generated-so-far
        (recompute on resume; exact under greedy decoding)."""
        e = self.running.pop(slot)
        self.kv.release(slot)
        if self.slab is not None:
            self.slab.release(slot)
        if e.req.out:
            gen = np.asarray(e.req.out, np.int32)
            e.prompt = np.concatenate([np.asarray(e.req.prompt, np.int32),
                                       gen])
        e.slot = -1
        e.prefilled = 0
        e.shared_tokens = 0
        e.shared_pages = []
        e.metrics.n_preemptions += 1
        self.preemptions += 1
        self.waiting.appendleft(e)
        return e

    def preempt_one(self, shard: int | None = None) -> _Entry | None:
        """Evict the youngest running sequence (LIFO victim policy) that
        actually owns pages — evicting a freshly admitted zero-page entry
        (chunked mode reserves the slot before any pages) frees nothing.
        With `shard` given, only sequences of that shard are candidates:
        pages freed in another shard cannot relieve this shard's
        pressure."""
        cands = self.running
        if shard is not None:
            cands = {s: e for s, e in self.running.items()
                     if self.kv.shard_of_slot(s) == shard}
        if not cands:
            return None
        owners = [s for s in cands if self.kv.owned_pages(s)]
        slot = max(owners or cands,
                   key=lambda s: self.running[s].metrics.t_admit)
        return self._preempt_slot(slot)

    def ensure_write_capacity(self, slot: int, start_tok: int,
                              end_tok: int):
        """Grow `slot` to hold end_tok tokens AND fork any shared page
        in the write range [start_tok, end_tok) (copy-on-write), evicting
        other sequences of the same shard while the pool is dry (the
        allocator reclaims index-only pages of that shard first).
        Returns (ok, copies): ok is False if `slot` itself got evicted;
        copies are (src, dst) page pairs the engine must apply to the
        device pool before the write."""
        shard = self.kv.shard_of_slot(slot)
        while True:
            try:
                self.kv.ensure(slot, end_tok)
                return True, self.kv.cow_for_write(slot, start_tok,
                                                   end_tok)
            except OutOfPages:
                others = [s for s in self.running
                          if s != slot
                          and self.kv.shard_of_slot(s) == shard]
                if others:
                    self.preempt_one(shard=shard)
                else:
                    self._preempt_slot(slot)
                if slot not in self.running:
                    return False, []

    # ---------------- completion ----------------
    def finish(self, slot: int, cached_tokens=None) -> None:
        """Complete a request. `cached_tokens` (engine-provided when a
        prefix index is attached) is the token sequence whose KV the
        slot's pages actually hold — prompt + generated-minus-last; it
        is inserted into the radix index *before* the slot's references
        are dropped, so the pages outlive the request and seed future
        prefix hits."""
        e = self.running.pop(slot)
        if (self.prefix is not None and cached_tokens is not None
                and len(cached_tokens)):
            n = self.kv.pages_for(len(cached_tokens))
            self.prefix.insert(cached_tokens,
                               self.kv.owned_pages(slot)[:n])
        self.kv.release(slot)
        if self.slab is not None:
            self.slab.release(slot)
        e.metrics.t_done = time.time()
        e.metrics.n_generated = len(e.req.out)
        e.req.done = True

    def metrics_summary(self, entries) -> dict:
        """Aggregate per-request metrics. Alongside the averages, the
        raw per-request TTFT/TPOT sample lists are exported so the
        bench subsystem (repro.bench.metrics) can report percentiles —
        tail latency is the serving number that matters, and an average
        hides it."""
        ms = [e.metrics for e in entries]
        done = [m for m in ms if m.t_done]
        ttft = [m.ttft_s for m in done]
        tpot = [m.tpot_s for m in done if m.n_generated > 1]
        out = {
            "n_done": len(done),
            "preemptions": self.preemptions,
            "ttft_avg_s": float(np.mean(ttft)) if ttft else 0.0,
            # average over the same filtered sample list as the
            # percentile export: single-token requests have no
            # after-first-token interval, and counting their 0.0s
            # deflated the average the percentiles didn't see
            "tpot_avg_s": float(np.mean(tpot)) if tpot else 0.0,
            "ttft_samples_s": ttft,
            "tpot_samples_s": tpot,
            "kv_high_water_pages": self.kv.high_water,
            "kv_usable_pages": self.kv.usable_pages,
            "pages_allocated": getattr(self.kv, "pages_allocated", 0),
            "cow_forks": getattr(self.kv, "cow_forks", 0),
            "prefix_hits": 0,
            "prefix_lookups": 0,
            "prefix_hit_rate": 0.0,
            "prefix_tokens_saved": 0,
            "prefix_cached_pages": 0,
            "prefix_evictions": 0,
            "slab_usable_slabs": 0,
            "slab_high_water": 0,
            "slabs_allocated": 0,
        }
        if self.slab is not None:
            out["slab_usable_slabs"] = self.slab.usable_slabs
            out["slab_high_water"] = self.slab.high_water
            out["slabs_allocated"] = self.slab.slabs_allocated
        if self.prefix is not None:
            out["prefix_hits"] = self.prefix.hits
            out["prefix_lookups"] = self.prefix.lookups
            out["prefix_hit_rate"] = self.prefix.hit_rate
            out["prefix_tokens_saved"] = self.prefix.tokens_saved
            out["prefix_cached_pages"] = self.prefix.cached_pages()
            out["prefix_evictions"] = self.prefix.evictions
        return out
