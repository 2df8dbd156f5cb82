"""The one traffic generator: reads a mix's parameters and yields
requests.  Nothing here knows a particular mix; a mix is a data file
under ``bench/traffic/``.

Lengths are lognormal (median, sigma), clipped to [min, max], with the
prompt plus output capped at ``max_total``.  They are drawn by
stratified blocks: every block of ``block`` consecutive requests (a
mix's parameter) holds one draw from each of ``block`` equal-probability
strata, in a random order, so any window of the run sees nearly the same
mix whatever the seed.  An open loop's gaps are exponential quantiles
drawn the same way, block by block: every ``block`` arrivals hold one
gap from each stratum, so the arrivals are Poisson in their marginal
and every seed offers the same load, burst for burst, in another order.
Token ids are uniform over the vocabulary.

A mix may fix its schedule (``schedule_seed``): its lengths, arrivals
and a closed loop's first residual outputs are then the same draw, in
the same order, for every run, and ``--seed`` draws only the prompts'
token ids (and the weights).  An open loop near its knee needs it: there
the order alone of the same lengths and gaps moves the TTFT tail of a
window by a fifth from seed to seed.  A closed loop's tokens per second
move with the share of chunk steps the order puts in the window.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class Planned:
    """One request as the client will send it."""
    index: int
    prompt: np.ndarray          # (P,) int32
    max_new: int
    due: float = 0.0            # seconds after the schedule's start


def _stratified(rng, n: int, block: int) -> np.ndarray:
    """n probability levels in (0, 1): each consecutive block of
    ``block`` holds one level from each stratum, shuffled."""
    out = np.empty(n)
    for start in range(0, n, block):
        m = min(block, n - start)
        levels = (rng.permutation(block)[:m] + rng.random(m)) / block
        out[start:start + m] = levels
    return out


def _lognormal(spec: dict, levels: np.ndarray) -> np.ndarray:
    mu = math.log(spec["median"])
    z = np.array([_NORMAL.inv_cdf(float(q)) for q in levels])
    v = np.exp(mu + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def _schedule_rng(mix: dict, seed: int, stream: int):
    return np.random.default_rng([int(mix.get("schedule_seed", seed)),
                                  stream])


def lengths(mix: dict, seed: int, n: int, block: int):
    """(prompt_lens, output_lens) of the first n requests."""
    rng = _schedule_rng(mix, seed, 1)
    p = _lognormal(mix["prompt"], _stratified(rng, n, block))
    o = _lognormal(mix["output"], _stratified(rng, n, block))
    o = np.maximum(1, np.minimum(o, mix["max_total"] - p))
    return p, o


def open_schedule(mix: dict, seed: int, seconds: float) -> List[float]:
    """Due times (seconds from the schedule's start) of an open loop at
    ``mix['rate']`` over ``seconds``: a fixed set of exponential gaps
    whose sum is the span, in a seeded order."""
    rate = float(mix["rate"])
    n = max(1, int(round(rate * seconds)))
    rng = _schedule_rng(mix, seed, 2)
    levels = _stratified(rng, n, int(mix["block"]))
    gaps = -np.log1p(-levels) / rate
    gaps *= seconds / gaps.sum()
    return list(np.cumsum(gaps) - gaps[0])


class Requests:
    """The seeded request stream of one run: lengths from the mix and
    prompts of uniform token ids."""

    def __init__(self, mix: dict, seed: int, vocab: int, n: int,
                 block: int):
        self.mix = mix
        self.vocab = vocab
        self.prompt_lens, self.output_lens = lengths(mix, seed, n, block)
        self._tok = np.random.default_rng([seed, 3])
        self._next = 0

    def __len__(self) -> int:
        return len(self.prompt_lens)

    def take(self, max_new: int = 0) -> Planned:
        """The next request; ``max_new`` overrides its output length."""
        i = self._next
        if i >= len(self):
            raise IndexError("request stream exhausted: raise the mix's draws")
        self._next += 1
        p = int(self.prompt_lens[i])
        o = int(max_new) if max_new else int(self.output_lens[i])
        o = max(1, min(o, int(self.mix["max_total"]) - p))
        prompt = self._tok.integers(0, self.vocab, p, dtype=np.int32)
        return Planned(i, prompt, o)


def residual_outputs(mix: dict, seed: int, n: int) -> List[int]:
    """What is left of the outputs of ``n`` requests caught part-way
    through their lives, as a closed loop in steady state holds them:
    the quantiles at (i + 0.5) / n of the residual life U * L, with L
    drawn in proportion to its length, the same for every seed, in the
    schedule's order."""
    grid = _lognormal(mix["output"], (np.arange(4096) + 0.5) / 4096)
    rng = np.random.default_rng(0x5EED)
    lengths = rng.choice(grid, size=65536, p=grid / grid.sum())
    resid = np.sort(np.ceil(rng.random(65536) * lengths))
    q = resid[((np.arange(n) + 0.5) / n * len(resid)).astype(int)]
    order = _schedule_rng(mix, seed, 5).permutation(n)
    return [max(1, int(v)) for v in q[order]]
