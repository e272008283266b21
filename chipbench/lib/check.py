"""The comparison that decides `correct`.

Once the window has closed, a sample of the finished requests, drawn
from the seed and holding the longest one, is scored by the plain
reference: the prompt followed by the served tokens, one pass. At each
served token the gap is the reference's best logit minus its logit of
the token that was served; greedy serving at the reference's own
precision gives 0. The widest gap over the sample is held against the
cell's limit (chipbench/cells/<cell>.json). Besides, every finished
request must have served exactly its budget of tokens, and every
request due in the window must have been answered.
"""
from __future__ import annotations

import json

import numpy as np

from chipbench.lib.spec import BENCH_DIR

# the reference's lead of its best logit over its second at which a
# position counts as confident
CONFIDENT = 0.3
NUMBERS = ("max_logit_gap", "mean_logit_gap", "confident_miss_share")
GAP_KEY = "mean_logit_gap"


def limits(cell: str) -> dict:
    """{number: limit} for the cell (chipbench/cells/<cell>.json); the
    numbers named there are the ones compared."""
    return json.loads((BENCH_DIR / "cells" / f"{cell}.json").read_text())


def sample(tracks, k: int, seed: int) -> list:
    """The longest finished request and k - 1 others drawn from the
    seed."""
    done = [t for t in tracks if t.req is not None and t.req.done]
    if not done:
        return []
    size = lambda t: (len(t.prompt) + len(t.req.out), len(t.req.out))
    longest = max(done, key=size)
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def rows(tracks) -> list:
    """(prompt + served tokens, index of the position that predicts the
    first served token) per request."""
    return [(np.concatenate([t.prompt, np.asarray(t.req.out, np.int32)]),
             len(t.prompt) - 1) for t in tracks]


def gap_numbers(scores, key: str = "served") -> dict:
    """How far the tokens of `key` ("served", or "ctrl" for the token a
    lower precision ranks first) lie below the reference's best logit,
    over every scored position:

      max_logit_gap         the widest gap
      mean_logit_gap        the mean gap
      confident_miss_share  among positions where the reference's best
                            leads its second by CONFIDENT or more, the
                            share whose token is not the best
    """
    if not scores:
        return {k: float("inf") for k in NUMBERS}
    gap = np.concatenate([s["best"] - s[key] for s in scores])
    lead = np.concatenate([s["best"] - s["second"] for s in scores])
    conf = lead >= CONFIDENT
    return {"max_logit_gap": float(np.max(gap)),
            "mean_logit_gap": float(np.mean(gap)),
            "confident_miss_share": float(np.mean(gap[conf] > 0))
            if conf.any() else 0.0}


def wrong_lengths(tracks) -> int:
    """Finished requests that did not serve exactly their budget (no
    stop token is set; the traffic keeps every sequence under the
    engine's capacity)."""
    return sum(1 for t in tracks
               if t.req is not None and t.req.done
               and len(t.req.out) != t.max_new)


def verdict(numbers: dict, wrong: int, unanswered: int, n_scored: int,
            limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): each number the cell's
    limits name, and the counts, beside its limit. Scores of the
    control go through the same call, with the control's numbers."""
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in limits.items()}
    checks.update(wrong_lengths={"value": wrong, "limit": 0},
                  unanswered={"value": unanswered, "limit": 0},
                  scored_tokens={"value": n_scored, "limit": 1})
    correct = (all(numbers[k] <= v for k, v in limits.items())
               and wrong == 0 and unanswered == 0 and n_scored >= 1)
    return correct, checks
