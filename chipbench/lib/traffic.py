"""The one traffic generator; a mix is a JSON file of its parameters
(chipbench/traffic/<name>.json):

  loop          "closed" (`clients` each send the next request when
                the last completes) or "open" (Poisson arrivals at
                `rate` requests/s)
  slots, max_len  the engine's batch slots and per-sequence capacity
  pages         pages in the engine's pool (default: every slot at
                max_len, plus the reserve page)
  block         requests per block: every block holds the same
                multiset of sizes (and gaps, and prefix choices), in an
                order drawn from the block's index alone, so a seed
                changes the token ids and not the work: in a closed
                loop the order decides which requests fall in the
                window
  prompt, output  {"median", "sigma", "min", "max"}: log-normal
                lengths, taken at the block's evenly spaced quantiles
                and clipped
  prefixes      optional {"count", "tokens", "weights"}: shared system
                prompts; each prompt is one of them (chosen in
                proportion to `weights`) followed by `prompt` tokens
  ramp_s        seconds of the same traffic before the window opens,
                clients starting evenly spread over it
  check_requests  finished requests the reference compares

Token ids are uniform over the vocabulary, drawn from the seed, but
for the first token after the shared prefix: it is the request's serial
number plus a seeded offset (mod the vocabulary), so no two requests of
a run share more than their system prompt. A chance match of even one
token would send the request down the extend path at a shape that no
warm-up compiled.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


ORDER_KEY = 0x0DE5       # the order of sizes within a block: not the seed


@dataclass
class Item:
    """One request as generated: its prompt, output budget, the gap
    before it (open loop) and which shared prefix it starts with."""
    prompt: np.ndarray
    max_new: int
    gap_s: float = 0.0
    prefix: int = -1


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """n log-normal sizes at evenly spaced quantiles, clipped."""
    nd = NormalDist()
    qs = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    x = [spec["median"] * math.exp(spec["sigma"] * q) for q in qs]
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n exponential gaps (mean 1/rate) at evenly spaced quantiles."""
    return np.array([-math.log(1 - (i + 0.5) / n) / rate
                     for i in range(n)])


def prefix_counts(weights, n: int) -> np.ndarray:
    """How many of n requests start with each prefix (largest
    remainder of n * weight)."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    c = np.floor(raw).astype(int)
    for j in np.argsort(-(raw - c))[: n - c.sum()]:
        c[j] += 1
    return c


class Traffic:
    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        nb = mix["block"]
        self.prompt_sizes = quantile_sizes(mix["prompt"], nb)
        self.output_sizes = quantile_sizes(mix["output"], nb)
        self.gaps = (exp_gaps(mix["rate"], nb) if mix["loop"] == "open"
                     else np.zeros(nb))
        self.first_base = int(
            np.random.default_rng([self.seed, 0xF1]).integers(self.vocab))
        pre = mix.get("prefixes")
        self.prefixes = []
        self.prefix_choice = np.full(nb, -1)
        if pre:
            rng = np.random.default_rng([self.seed, 0x5EED])
            self.prefixes = [
                rng.integers(0, self.vocab, pre["tokens"], dtype=np.int32)
                for _ in range(pre["count"])]
            self.prefix_choice = np.repeat(
                np.arange(pre["count"]),
                prefix_counts(pre["weights"], nb))

    def block(self, b: int) -> list[Item]:
        """Block b of the stream: the fixed sizes in the block's own
        order, token ids from the seed."""
        order = np.random.default_rng([ORDER_KEY, b])
        rng = np.random.default_rng([self.seed, b])
        nb = self.mix["block"]
        ps = order.permutation(self.prompt_sizes)
        os_ = order.permutation(self.output_sizes)
        gs = order.permutation(self.gaps)
        pc = order.permutation(self.prefix_choice)
        items = []
        for j in range(nb):
            toks = rng.integers(0, self.vocab, int(ps[j]), dtype=np.int32)
            toks[0] = (self.first_base + b * nb + j) % self.vocab
            if pc[j] >= 0:
                toks = np.concatenate([self.prefixes[pc[j]], toks])
            items.append(Item(toks, int(os_[j]), float(gs[j]), int(pc[j])))
        return items

    def stream(self):
        b = 0
        while True:
            yield from self.block(b)
            b += 1

    def prompt_lengths(self) -> np.ndarray:
        """Every prompt length the stream can hold (prefix included)."""
        n = len(self.prefixes[0]) if self.prefixes else 0
        return np.unique(self.prompt_sizes) + n
