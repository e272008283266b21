"""CPU tests of how the benchmark reads the serving engine's spans
(lib/spans.py): idle time attributed to the innermost program span in
a trace, the per-phase sums against lib/trace.py's reduction, and the
per-layer metrics that read the engine's ring.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

from types import SimpleNamespace

import pytest

import chipbench.run as run
from chipbench.lib import spans, trace
from chipbench.lib.serve_loop import drive
from chipbench.lib.spec import metric_reader
from chipbench.tests import tiny
from chipbench.tests.test_yardstick import _events

NEW_METRICS = ("step.host_ms_per_tick.batch", "step.tick_gap_p95_ms.chat")


def _with_spans():
    """_events() (idle [0,10], [40,60], [70,100]; phases decode_tick
    [5,45], admit [45,80], arrival_wait [80,100]) with program spans: a
    decode tick [6,44] holding a wait [30,40] and a readback [41,43],
    and an admission [62,75] holding its first token [70,74]."""
    tr = _events()
    tr["spans"] = [("serve.decode.tick", 6.0, 44.0),
                   ("serve.decode.wait", 30.0, 40.0),
                   ("serve.decode.readback", 41.0, 43.0),
                   ("serve.admit", 62.0, 75.0),
                   ("serve.admit.first_token", 70.0, 74.0)]
    return tr


def test_idle_goes_to_the_innermost_open_span():
    r = spans.idle_split(_with_spans())
    got = {k: v * 1e9 for k, v in r["idle_gaps"]}
    assert got == pytest.approx({
        "arrival_wait": 20, "admit": 20, "serve.decode.tick": 6,
        "none": 5, "serve.admit.first_token": 4,
        "serve.decode.readback": 2, "decode_tick": 2, "serve.admit": 1})
    # busy [10,40] and [60,70]; spans cover all of the first and [62,70]
    assert r["busy_in_spans"] == pytest.approx(38 / 40)


def test_each_phase_keeps_what_its_spans_do_not_take():
    tr = _with_spans()
    old = dict(trace.reduce_events(tr)["breakdown"]["idle_gaps"])
    by = spans.idle_split(tr)["by_phase"]
    assert set(by) == set(old)
    for phase, v in by.items():
        assert v["total"] == pytest.approx(old[phase], rel=1e-12)
        assert v["keeps"] + sum(v["spans"].values()) == pytest.approx(
            old[phase], rel=1e-12)
    assert {k: x * 1e9 for k, x in by["admit"]["spans"].items()} == \
        pytest.approx({"serve.admit": 1, "serve.admit.first_token": 4})


def test_without_spans_the_split_is_the_old_reduction():
    tr = _events()
    assert spans.idle_split(tr)["idle_gaps"] == \
        trace.reduce_events(tr)["breakdown"]["idle_gaps"]
    tr["spans"] = []
    assert spans.idle_split(tr)["idle_gaps"] == \
        trace.reduce_events(tr)["breakdown"]["idle_gaps"]


def test_self_time_and_innermost_pieces():
    s = _with_spans()["spans"]
    st = spans.self_times(s)
    assert st["serve.decode.tick"] == [1, 38.0, 26.0]
    assert st["serve.admit"] == [1, 13.0, 9.0]
    assert spans.innermost(s) == [
        (6.0, 30.0, "serve.decode.tick"), (30.0, 40.0, "serve.decode.wait"),
        (40.0, 41.0, "serve.decode.tick"),
        (41.0, 43.0, "serve.decode.readback"),
        (43.0, 44.0, "serve.decode.tick"), (62.0, 70.0, "serve.admit"),
        (70.0, 74.0, "serve.admit.first_token"), (74.0, 75.0, "serve.admit")]


# ------------------------------------------------------ the ring readers

def _ring_run(ring, trace_span=(1.0, 2.0), window=(1.0, 10.0), slots=4):
    eng = SimpleNamespace(recorder=SimpleNamespace(ring=ring), B=slots)
    win = SimpleNamespace(t0=window[0], t1=window[1], trace=trace_span)
    return SimpleNamespace(loop=SimpleNamespace(eng=eng), window=win)


def _tick(t, rows, wait_ms, gap_ms):
    """Records of one decode tick whose dispatch starts at t (s): 2 ms
    of host work before it, 1 ms of dispatch, the wait, 4 ms after it.
    Returns them and the next tick's dispatch start, which lies 2 ms +
    gap_ms after the wait's end."""
    ns = lambda s: int(round(s * 1e9))
    d0, w0 = t, t + 1e-3
    w1 = w0 + wait_ms / 1e3
    t1 = w1 + 4e-3
    step, tick = "serve.decode.step", "serve.decode.tick"
    return [("serve.decode.dispatch", ns(d0), ns(w0), 0),
            ("serve.decode.wait", ns(w0), ns(w1), 0),
            (step, ns(d0), ns(w1), rows),
            (tick, ns(t - 2e-3), ns(t1), rows)], \
        w1 + gap_ms / 1e3 + 2e-3


def test_ring_readers_by_hand():
    ring = [("serve.admit", int(0.5e9), int(0.6e9), 0)]
    t = 2.5
    for rows, gap in ((4, 10.0), (2, 20.0), (3, 30.0)):
        recs, t = _tick(t, rows, wait_ms=50.0, gap_ms=gap)
        ring += recs
    run_ = _ring_run(ring)
    recs = spans.window_records(run_)
    assert len(recs) == 12
    # 2 ms before the dispatch, 1 ms of it, 4 ms after the wait
    assert spans.host_ms_per_tick(recs) == pytest.approx(7.0, abs=1e-6)
    # the last tick has no next dispatch
    assert spans.tick_gaps_ms(recs) == pytest.approx([12.0, 22.0],
                                                     abs=1e-6)
    vals = {m: metric_reader(m).read(run_) for m in NEW_METRICS}
    assert vals["step.host_ms_per_tick.batch"] == pytest.approx(7.0,
                                                                abs=1e-6)
    assert 12.0 < vals["step.tick_gap_p95_ms.chat"] <= 22.0


def test_ring_readers_refuse_a_ring_that_misses_the_start(capsys):
    recs, _ = _tick(2.5, 4, 50.0, 10.0)
    run_ = _ring_run(recs)           # oldest record after the trace end
    for m in NEW_METRICS:
        assert metric_reader(m).read(run_) is None
    assert "WARNING" in capsys.readouterr().err


def test_ring_readers_read_nothing_from_a_program_without_spans():
    run_ = _ring_run([])
    del run_.loop.eng.recorder
    for m in NEW_METRICS:
        assert metric_reader(m).read(run_) is None


def test_ring_readers_on_a_smoke_run(monkeypatch):
    """The untraced harness on a smoke-size engine: every new metric
    reads a value over [W0, W1]."""
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    eng, loop, traffic = run.build(tiny.config(), tiny.mix("closed"),
                                   2 ** 33 + 5)
    win = drive(loop, traffic, 1.5)
    run_ = SimpleNamespace(loop=loop, window=win)
    recs = spans.window_records(run_)
    assert recs and all(win.t0 * 1e9 <= r[1] for r in recs)
    vals = {m: metric_reader(m).read(run_) for m in NEW_METRICS}
    assert vals["step.host_ms_per_tick.batch"] > 0
    assert vals["step.tick_gap_p95_ms.chat"] >= 0
    # the recorder's step total over the window is the engine's decode_s
    a, b = win.stats0, win.stats1
    assert b["decode_s"] - a["decode_s"] == pytest.approx(
        b["serve.decode.step_s"] - a.get("serve.decode.step_s", 0.0))
