"""Prefill (or extend) time per thousand new prompt tokens: the
engine's own `prefill_s` over its `prefill_tokens`, over the window."""


def read(run):
    a, b = run.window.stats0, run.window.stats1
    n = b["prefill_tokens"] - a["prefill_tokens"]
    return 1e6 * (b["prefill_s"] - a["prefill_s"]) / n if n else None
