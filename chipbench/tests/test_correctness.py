"""The check that decides `correct`, driven through a whole run at a
smoke size on the CPU (the look for a chip is skipped): a sound run
passes, the control fails, and a run with the timed path broken
underneath fails.

The control is the reference in the program's place at the next
precision down: float8 e4m3 matmul operands for a model served in
bf16. It need not decode: at every position of the served requests the
check reads the gap of the token the control ranks first.
"""
from __future__ import annotations

import gc

import jax.numpy as jnp
import pytest

import chipbench.run as run
from chipbench.lib import check, spec
from chipbench.lib.serve_loop import drive
from chipbench.tests import tiny

CELL = "qwen3-4b.decode"
SEED = 2 ** 33 + 77


@pytest.fixture(autouse=True)
def no_disk_cache(monkeypatch):
    monkeypatch.setattr(run, "enable_cache", lambda: None)


def _run(limit, hook=None, mix=None):
    return run.run_cell(CELL, SEED, 1.5, False, config=tiny.config(),
                        mix=mix or tiny.mix("open", prefixes=True),
                        limits={check.GAP_KEY: limit}, engine_hook=hook)


def test_control_separates_from_the_program():
    """Program and control on the same served requests: the control's
    widest gap is at least three times the program's, so a limit
    between them passes the one and fails the other."""
    config, mix = tiny.config(), tiny.mix("closed")
    eng, loop, traffic = run.build(config, mix, SEED)
    win = drive(loop, traffic, 1.5)
    picked = check.sample(win.tracks, 4, SEED)
    del eng, loop
    gc.collect()
    scores, n = run.score_sample(config, SEED, picked, control=True)
    prog = check.gap_numbers(scores)
    ctrl = check.gap_numbers(scores, "ctrl")
    assert n > 20
    k = check.GAP_KEY
    assert ctrl[k] >= 3 * prog[k], (prog, ctrl)
    limits = {k: (prog[k] * ctrl[k]) ** 0.5 if prog[k] else ctrl[k] / 3}
    assert check.verdict(prog, 0, 0, n, limits)[0]
    assert not check.verdict(ctrl, 0, 0, n, limits)[0]


def test_sound_run_is_correct():
    r = _run(limit=0.05)
    assert r["correct"], r["checks"]
    assert r["checks"]["scored_tokens"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in spec.cell_metrics(
        CELL, "end_to_end")}


def test_altered_tokens_are_caught():
    """Every decode step's tokens altered where they are produced (the
    step's logits shifted by one vocabulary entry)."""
    def hook(eng):
        step = eng._decode

        def broken(*a):
            logits, cache = step(*a)
            return jnp.roll(logits, 1, axis=-1), cache
        eng._decode = broken

    r = _run(limit=0.05, hook=hook)
    assert not r["correct"]
    assert r["checks"][check.GAP_KEY]["value"] > 0.05


def test_short_outputs_are_caught():
    """A finished request that served less than its budget."""
    def hook(eng):
        finish = eng._finish

        def cut(e):
            if len(e.req.out) > 2:
                e.req.out.pop()
            finish(e)
        eng._finish = cut

    r = _run(limit=0.05, hook=hook)
    assert not r["correct"]
    assert r["checks"]["wrong_lengths"]["value"] > 0
