"""The serving-loop adapter: the body of `ServeEngine.run`
(serve/engine.py) as that method has it -- `sched.submit`, then
`sched.try_admit` / `_admit` until nothing more fits, `_prefill_tick`
(chunked prefill only), `_decode_tick` -- with one difference:
requests are submitted when they fall due, not all at once.

The adapter records what the engine does not: each request's due time,
the host time at which each of its tokens became visible (after its
admission, and after each decode tick), the span of every admission
and decode tick, and the live context of each tick. A public
submit/step API on the engine would replace this file.

All times are `time.time()`, the clock the scheduler stamps `t_admit`
with.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

PHASES = ("arrival_wait", "admit", "prefill_tick", "decode_tick",
          "host_bookkeeping")
DRAIN_S = 60.0          # wait this long past the window for first tokens


@dataclass
class Track:
    prompt: np.ndarray
    max_new: int
    due: float
    req: object = None
    warm: bool = False
    client: int = -1
    t_admit: float = 0.0
    n_prefix: int = 0
    t_tokens: list = field(default_factory=list)
    t_done: float = 0.0

    @property
    def t_first(self) -> float:
        return self.t_tokens[0] if self.t_tokens else 0.0


class Loop:
    def __init__(self, eng, *, annotate: bool = False):
        from repro.serve import Request
        self.Request = Request
        self.eng = eng
        self.annotate = annotate
        self.by_req: dict[int, Track] = {}
        self.active: list[Track] = []
        self.finished: list[Track] = []
        self.admissions: list[tuple] = []  # (t0, t1, prompt len, shared)
        self.ticks: list[tuple] = []       # (t0, t1, live rows, sum ctx)

    def phase(self, name: str):
        if self.annotate:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def submit(self, tr: Track) -> Track:
        tr.req = self.Request(prompt=tr.prompt, max_new_tokens=tr.max_new)
        self.by_req[id(tr.req)] = tr
        self.eng.sched.submit(tr.req)
        self.active.append(tr)
        return tr

    @staticmethod
    def _see(tr: Track, t: float) -> None:
        while len(tr.t_tokens) < len(tr.req.out):
            tr.t_tokens.append(t)

    def iterate(self) -> list[Track]:
        """One pass of the engine's loop; returns the requests that
        finished in it."""
        eng = self.eng
        with self.phase("admit"):
            while True:
                e = eng.sched.try_admit()
                if e is None:
                    break
                tr = self.by_req[id(e.req)]
                tr.t_admit = e.metrics.t_admit
                tr.n_prefix = e.shared_tokens
                t0 = time.time()
                eng._admit(e)
                t1 = time.time()
                self.admissions.append((t0, t1, len(e.prompt),
                                        e.shared_tokens))
                self._see(tr, t1)
        if eng.cache_kind == "paged" and eng.prefill_chunk:
            with self.phase("prefill_tick"):
                eng._prefill_tick()
        with self.phase("decode_tick"):
            running = eng.sched.running
            live = len(running)
            ctx = int(sum(int(eng.pos[s]) + 1 for s in running))
            n0 = eng.stats["ticks"]
            t0 = time.time()
            eng._decode_tick()
            t1 = time.time()
            if eng.stats["ticks"] != n0:
                self.ticks.append((t0, t1, live, ctx))
        with self.phase("host_bookkeeping"):
            done, still = [], []
            for tr in self.active:
                self._see(tr, t1)
                if tr.req.done:
                    tr.t_done = tr.t_tokens[-1]
                    done.append(tr)
                else:
                    still.append(tr)
            self.active = still
            self.finished.extend(done)
        return done

    def run_until_idle(self, limit_s: float = 600.0) -> None:
        t_end = time.time() + limit_s
        while self.eng.sched.has_work():
            if time.time() > t_end:
                raise RuntimeError("engine did not drain")
            self.iterate()

    def clear_records(self) -> None:
        self.finished.clear()
        self.admissions.clear()
        self.ticks.clear()


@dataclass
class Window:
    t0: float
    t1: float
    stats0: dict
    stats1: dict
    tracks: list            # every request submitted after the warm-up
    trace: tuple | None     # (t0, t1) of the traced part, or None


def drive(loop: Loop, traffic, seconds: float, *, trace_s: float = 0.0,
          tracer=None) -> Window:
    """Offer the mix for ramp_s + `seconds`, then drain until every
    request due in the window has its first token (at most DRAIN_S).
    `tracer` (with start()/stop()) is started as the window opens and
    stopped after `trace_s` seconds, at an iteration boundary."""
    mix = traffic.mix
    stream = traffic.stream()
    closed = mix["loop"] == "closed"
    eng = loop.eng
    tracks: list[Track] = []
    T0 = time.time()
    W0 = T0 + mix["ramp_s"]
    if closed:
        n = mix["clients"]
        next_due = [T0 + i * mix["ramp_s"] / n for i in range(n)]
    else:
        next_due = [T0]
        pending = None
    opened = False
    tr_span = None
    tr_ann = None
    stats0 = None

    def new(due, client=-1):
        it = next(stream)
        tr = Track(it.prompt, it.max_new, due, client=client)
        tracks.append(loop.submit(tr))
        return it

    while True:
        now = time.time()
        if not opened and now >= W0:
            opened = True
            if tracer is not None:
                tracer.start()
                tr_ann = loop.phase("trace_window")
                tr_ann.__enter__()
                tr_span = [time.time(), None]
            W0 = time.time()
            stats0 = dict(eng.stats)
        if opened:
            if tr_span is not None and tr_span[1] is None \
                    and now >= tr_span[0] + trace_s:
                tr_span[1] = time.time()
                tr_ann.__exit__(None, None, None)
                tracer.stop()
            if now >= W0 + seconds:
                break
        if closed:
            for c, due in enumerate(next_due):
                if due is not None and due <= now:
                    new(due, c)
                    next_due[c] = None
        else:
            if pending is None:
                pending = next(stream)
                pending_due = next_due[0] + pending.gap_s
            while pending_due <= now:
                tracks.append(loop.submit(Track(pending.prompt,
                                                pending.max_new,
                                                pending_due)))
                next_due[0] = pending_due
                pending = next(stream)
                pending_due = next_due[0] + pending.gap_s
        if not loop.active:
            wake = min(d for d in next_due if d is not None) if closed \
                else pending_due
            with loop.phase("arrival_wait"):
                time.sleep(max(0.0, min(wake, W0 + seconds
                                        if opened else W0) - time.time()))
            continue
        for tr in loop.iterate():
            if closed and tr.client >= 0:
                next_due[tr.client] = tr.t_done
    W1 = time.time()
    stats1 = dict(eng.stats)
    if tr_span is not None and tr_span[1] is None:
        tr_span[1] = time.time()
        tr_ann.__exit__(None, None, None)
        tracer.stop()
    late = lambda: [t for t in tracks
                    if W0 <= t.due < W1 and not t.t_tokens]
    t_end = W1 + DRAIN_S
    while late() and time.time() < t_end and eng.sched.has_work():
        loop.iterate()
    return Window(W0, W1, stats0, stats1, tracks,
                  tuple(tr_span) if tr_span else None)


def warm_up(loop: Loop, traffic, max_len: int, page_size: int) -> int:
    """Compile every shape the mix's traffic reaches, through the
    engine's own calls: each shared prefix once (which also leaves it in
    the radix index, as the traffic needs), then one request per prompt
    bucket, or per (suffix bucket, pages) of the extend path when
    prompts share a prefix. Each warm request decodes once. Then the
    block-table update at every power-of-two row count. Returns the
    number of warm requests."""
    import jax.numpy as jnp
    from repro.serve.engine import bucket_len
    eng = loop.eng
    vocab = traffic.vocab
    rng = np.random.default_rng([traffic.seed, 0xA11])
    serial = [0]

    def prompt(n):
        p = rng.integers(0, vocab, n, dtype=np.int32)
        serial[0] += 1
        p[0] = (traffic.first_base - serial[0]) % vocab
        return p

    n = 0
    for pre in traffic.prefixes:
        loop.submit(Track(pre.copy(), 1, time.time(), warm=True))
        n += 1
    loop.run_until_idle()
    keys = {}
    npre = len(traffic.prefixes[0]) if traffic.prefixes else 0
    for L in traffic.prompt_lengths():
        L = int(L)
        if npre:
            key = (bucket_len(L - npre, max_len), -(-L // page_size))
        else:
            key = bucket_len(L, max_len)
        keys[key] = max(keys.get(key, 0), L)
    for L in keys.values():
        p = prompt(L - npre)
        if npre:
            p = np.concatenate([traffic.prefixes[0], p])
        loop.submit(Track(p, 2, time.time(), warm=True))
        n += 1
    loop.run_until_idle()
    B = eng.B
    k = 1
    while k <= B:
        idx = np.arange(k)
        eng._bt_dev = eng._bt_update(
            eng._bt_dev, jnp.asarray(idx, jnp.int32),
            jnp.asarray(eng.kv.block_tables[idx], jnp.int32))
        k *= 2
    eng._bt_dev.block_until_ready()
    loop.clear_records()
    return n
