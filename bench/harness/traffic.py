"""One general traffic generator; every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) is an open loop of independent
requests. Parameters of a mix:

  rate_per_s       requests arriving per second (Poisson)
  prompt           prompt length: lognormal median, sigma, clip min/max
  output           reply length (max_new_tokens): lognormal, clipped
  warm_start       requests already resident when the window opens: drawn
                   from the same lengths, each with a share of its reply
                   still to come, and prefilled during set-up, so that the
                   window starts near the occupancy the rate sustains
                   instead of from an empty engine (rate x lifetime)

Every seed gets the same work: the lengths and gaps are fixed quantiles of
their distributions for the window's size, dealt out in an order fixed by
the mix's ``schedule_seed``. The run's seed draws the token ids (and,
elsewhere, the weights). With greedy decoding and no stop token the ids
change no amount of work, so two seeds differ only in what is computed,
never in how much or when.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_NORMAL = NormalDist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: Dict, rng: np.random.Generator
                      ) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal (median, sigma),
    clipped to [min, max], in an order drawn from ``rng``."""
    z = np.array([_NORMAL.inv_cdf(p) for p in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def exponential_values(n: int, mean: float, rng: np.random.Generator
                       ) -> np.ndarray:
    """``n`` values at the quantiles of an exponential of ``mean``."""
    return rng.permutation(-mean * np.log1p(-_quantiles(n)))


@dataclasses.dataclass
class Arrival:
    due: float                    # seconds after the window opens
    prompt: np.ndarray
    max_new_tokens: int


def generate(mix: Dict, seconds: float, seed: int, vocab_size: int
             ) -> List[Arrival]:
    """The requests a window of ``seconds`` receives under ``mix``, in the
    order they come due."""
    rng = np.random.default_rng(mix.get("schedule_seed", 0))
    ids = np.random.default_rng(seed)
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    # Arrival gaps at exponential quantiles, scaled so the n arrivals
    # fill the window exactly: the offered rate is n / seconds.
    gaps = exponential_values(n, 1.0, rng)
    gaps *= seconds / gaps.sum()
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = lognormal_lengths(n, mix["prompt"], rng)
    outs = lognormal_lengths(n, mix["output"], rng)
    return [Arrival(float(dues[i]),
                    ids.integers(1, vocab_size, int(prompts[i]))
                    .astype(np.int32), int(outs[i])) for i in range(n)]


def warm_start(mix: Dict, seed: int, vocab_size: int) -> List[Arrival]:
    """The requests resident when the window opens (``warm_start`` of
    them), with the mix's lengths, of which the k-th of n has
    (k + 0.5) / n of its reply still to come."""
    n = int(mix.get("warm_start", 0))
    if not n:
        return []
    rng = np.random.default_rng([mix.get("schedule_seed", 0), 1])
    ids = np.random.default_rng([seed, 1])
    prompts = lognormal_lengths(n, mix["prompt"], rng)
    outs = lognormal_lengths(n, mix["output"], rng)
    left = rng.permutation(_quantiles(n))
    return [Arrival(0.0, ids.integers(1, vocab_size, int(prompts[i]))
                    .astype(np.int32),
                    max(1, int(np.ceil(outs[i] * left[i]))))
            for i in range(n)]


def longest_context(mix: Dict) -> int:
    """The most tokens one request of ``mix`` can hold: prompt and reply."""
    return mix["prompt"]["max"] + mix["output"]["max"]
