"""Spans and counters of the serving engine (serve/trace.py).

A tiny paged engine on the CPU: spans nest in time under the right
parents,
the long-standing `decode_s` / `prefill_s` accumulators are exactly the
totals of the spans that feed them, the counters agree with the
scheduler's own view, admission spans carry request ids, the ring
stays bounded, `stats` holds flat numbers only, and the spans land in
the profiler's trace under their names.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serve import Request, ServeEngine
from repro.serve import trace
from repro.serve.trace import RING, Recorder

KEY = jax.random.PRNGKey(0)
PAGE = 16

# parent of every span the engine and scheduler write, per mode
PARENTS = {
    "serve.sched.admit": None,
    "serve.admit": None,
    "serve.admit.cow": "serve.admit",
    "serve.admit.forward": "serve.admit",
    "serve.admit.first_token": "serve.admit",
    "serve.prefill_chunk": None,
    "serve.decode.tick": None,
    "serve.decode.grow": "serve.decode.tick",
    "serve.decode.step": "serve.decode.tick",
    "serve.decode.readback": "serve.decode.tick",
    "serve.decode.emit": "serve.decode.tick",
    "serve.decode.bt_sync": "serve.decode.step",
    "serve.decode.dispatch": "serve.decode.step",
    "serve.decode.wait": "serve.decode.step",
}

_params = {}


def _cfg():
    return get_config("tiny-lm").replace(dtype="float32", n_layers=2,
                                         d_model=64, d_ff=128, remat="none")


def _p():
    if "p" not in _params:
        _params["p"] = init_params(_cfg(), KEY)
    return _params["p"]


def _engine(**kw):
    return ServeEngine(_cfg(), _p(), batch_size=3, max_len=128,
                       dtype="float32", cache_kind="paged", page_size=PAGE,
                       **kw)


def _requests(n=5):
    """Requests sharing a 2-page prefix, so later ones hit the index."""
    prefix = (np.arange(2 * PAGE, dtype=np.int32) * 3 + 5) % 199
    reqs = []
    for i in range(n):
        tail = (np.arange(5 + i, dtype=np.int32) * 7 + i + 1) % 199
        reqs.append(Request(prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=4 + i % 3))
    return reqs


def _drive(eng, reqs):
    """The body of ServeEngine.run without its closing summary, as the
    benchmark's loop drives the engine."""
    for r in reqs:
        eng.sched.submit(r)
    while eng.sched.has_work():
        while True:
            e = eng.sched.try_admit()
            if e is None:
                break
            eng._admit(e)
        if eng.prefill_chunk:
            eng._prefill_tick()
        eng._decode_tick()
    return reqs


@pytest.mark.parametrize("mode", ["plain", "chunked", "speculative"])
def test_spans_nest_under_their_parents(mode):
    kw = {"plain": {}, "chunked": {"prefill_chunk": 8},
          "speculative": {"speculate": 2, "draft_params": _p()}}[mode]
    eng = _engine(**kw)
    _drive(eng, _requests())
    ring = list(eng.recorder.ring)
    names = {r[0] for r in ring}
    want = {"serve.sched.admit", "serve.admit", "serve.decode.tick",
            "serve.decode.grow", "serve.decode.step", "serve.decode.emit"}
    assert want <= names
    if mode == "speculative":
        # the speculative tick is timed no finer than its step
        assert not names & {"serve.decode.bt_sync", "serve.decode.dispatch",
                            "serve.decode.wait", "serve.decode.readback"}
    else:
        assert {"serve.decode.bt_sync", "serve.decode.dispatch",
                "serve.decode.wait", "serve.decode.readback"} <= names
    if mode == "chunked":
        assert "serve.prefill_chunk" in names
    for r in ring:
        name, t0, t1, _ = r
        want_parent = PARENTS[name]
        if name == "serve.admit.first_token" and mode == "chunked":
            want_parent = "serve.prefill_chunk"
        assert t0 <= t1
        # the parent is the shortest other record enclosing it in time
        around = [p for p in ring
                  if p is not r and p[1] <= t0 and t1 <= p[2]]
        parent = (min(around, key=lambda p: p[2] - p[1])[0]
                  if around else None)
        assert parent == want_parent, name
    # children close before their parent: the ring is in closing order
    ends = [r[2] for r in ring]
    assert ends == sorted(ends)


@pytest.mark.parametrize("mode", ["plain", "chunked"])
def test_span_totals_are_the_stats_accumulators(mode):
    eng = _engine(**({"prefill_chunk": 8} if mode == "chunked" else {}))
    _drive(eng, _requests())
    st = eng.stats
    assert st["decode_s"] > 0 and st["prefill_s"] > 0
    # the same durations added in the same order
    assert st["decode_s"] == st["serve.decode.step_s"]
    assert st["prefill_s"] == pytest.approx(
        st.get("serve.admit_s", 0.0) + st.get("serve.prefill_chunk_s", 0.0),
        rel=1e-12)
    ring = list(eng.recorder.ring)
    assert sum(r[0] == "serve.decode.step" for r in ring) == st["ticks"]
    step = sum(r[2] - r[1] for r in ring if r[0] == "serve.decode.step")
    assert step / 1e9 == pytest.approx(st["decode_s"], rel=1e-9)


def test_counters_match_the_scheduler():
    eng = _engine()
    reqs = _drive(eng, _requests())
    st = eng.stats
    entries = list(eng.sched.running.values())
    assert not entries and eng.sched.preemptions == 0
    n = len(reqs)
    assert st["admit_prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    # the prefix tokens the index attached, as the scheduler counted them
    assert st["admit_prefix_tokens"] == eng._prefix.tokens_saved > 0
    # every decoded row emits one token
    ring = list(eng.recorder.ring)
    steps = [r for r in ring if r[0] == "serve.decode.step"]
    assert st["decode_rows"] == sum(r[3] for r in steps) == st["tokens"]
    assert 0 < st["bt_rows_synced"] <= eng.B * (st["ticks"] + n)


def test_admission_spans_carry_distinct_request_ids():
    eng = _engine()
    reqs = _drive(eng, _requests())
    ring = list(eng.recorder.ring)
    admits = [r[3] for r in ring if r[0] == "serve.admit"]
    assert sorted(admits) == list(range(len(reqs)))
    firsts = [r[3] for r in ring if r[0] == "serve.admit.first_token"]
    assert sorted(firsts) == list(range(len(reqs)))
    assert {r[3] for r in ring if r[0] == "serve.sched.admit"} == set(admits)
    # a decode tick carries its live rows, its step the rows it decoded
    for r in ring:
        if r[0] in ("serve.decode.tick", "serve.decode.step"):
            assert 1 <= r[3] <= eng.B


def test_ring_stays_bounded(monkeypatch):
    clock = iter(range(0, 10**9, 1000))      # ns, one step a reading
    monkeypatch.setattr(trace.time, "time_ns", lambda: next(clock))
    stats = {}
    rec = Recorder(stats, maxlen=8)
    for i in range(20):
        with rec.span("serve.x", i):
            with rec.span("serve.x.y"):
                pass
    assert len(rec.ring) == 8
    assert [r[3] for r in rec.ring if r[0] == "serve.x"][-1] == 19
    # totals keep counting what the ring dropped: 20 spans of three
    # readings' time around 20 of one
    assert stats["serve.x_s"] == pytest.approx(20 * 3000e-9)
    assert stats["serve.x.y_s"] == pytest.approx(20 * 1000e-9)
    monkeypatch.undo()
    eng = _engine()
    assert eng.recorder.ring.maxlen == RING
    assert eng.sched.recorder is eng.recorder


def test_stats_keys_are_flat_numbers():
    eng = _engine(prefill_chunk=8)
    _drive(eng, _requests())
    st = eng.stats
    assert any(k.startswith("serve.") for k in st)
    for k, v in st.items():
        assert type(v) in (int, float), (k, type(v))
    snap = dict(st)
    eng._decode_tick()
    eng.recorder.count("admit_prompt_tokens", 1)
    assert snap["admit_prompt_tokens"] == st["admit_prompt_tokens"] - 1


def test_spans_land_in_the_profiler_trace(tmp_path):
    eng = _engine()
    reqs = _requests(2)
    _drive(eng, reqs[:1])          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive(eng, reqs[1:])
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    seen = {ev.name for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")}
    assert {"serve.admit", "serve.decode.step", "serve.decode.wait",
            "serve.sched.admit"} <= seen
