"""Traffic the benchmark owns: serving lengths and arrivals, and the store's
read-back keys after the window.

Every seed gets the same multiset of lengths and of gaps between
arrivals, in another order: the quantiles of the stated distributions,
permuted by the seed.  So runs of different seeds do the same amount of
work, and the seed changes only which request comes when and the tokens
they carry.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator of one named stream of a run's seed."""
    return np.random.default_rng([abs(int(seed)), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                      multiple: int = 1) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal with this median and
    sigma, rounded up to ``multiple`` (to nearest where it is 1) and
    clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = median * np.exp(sigma * z)
    x = np.ceil(x / multiple) * multiple if multiple > 1 else np.rint(x)
    return np.clip(x, lo, hi).astype(np.int64)


def poisson_due_times(n: int, seconds: float) -> np.ndarray:
    """``n`` gaps at the quantiles of an exponential, scaled to sum to
    ``seconds`` (rate ``n / seconds``), unordered."""
    gaps = -np.log1p(-_quantiles(n))
    return gaps * (seconds / gaps.sum())


class StratifiedPoisson:
    """Poisson arrivals at ``rate`` as ``run_open_loop`` takes them: over a
    span of ``duration`` the ``round(rate * duration)`` gaps at the
    quantiles of the exponential, permuted by the run's seed (the
    runner's own generator is not used)."""

    def __init__(self, rate: float, seed: int):
        self.rate, self.seed = float(rate), seed

    @property
    def name(self) -> str:
        return f"poisson-stratified({self.rate:g})"

    def times(self, rng, duration: float) -> np.ndarray:
        n = max(1, int(round(self.rate * duration)))
        gaps = rng_for(self.seed, 7).permutation(poisson_due_times(n, duration))
        return np.cumsum(gaps) - gaps


def chat_requests(traffic: Dict, seconds: float, seed: int,
                  vocab: int) -> List[Dict]:
    """Open-loop chat requests due in [0, ``seconds``): dicts with ``rid``,
    ``due`` (s after the window opens), ``prompt`` (int32 token ids,
    uniform over the vocabulary) and ``max_new``."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    p, o = traffic["prompt"], traffic["output"]
    prompts = lognormal_lengths(n, p["median"], p["sigma"], p["min"],
                                p["max"], p.get("multiple", 1))
    outs = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"])
    gaps = poisson_due_times(n, seconds)
    order = rng_for(seed, 1)
    prompts, outs, gaps = (order.permutation(prompts), order.permutation(outs),
                           order.permutation(gaps))
    due = np.cumsum(gaps) - gaps
    tokens = rng_for(seed, 2)
    return [{"rid": i, "due": float(due[i]),
             "prompt": tokens.integers(0, vocab, int(prompts[i]),
                                       dtype=np.int32),
             "max_new": int(outs[i])} for i in range(n)]


def warmup_lengths(traffic: Dict) -> List[int]:
    """Every prompt length the mix can draw (its multiples in range)."""
    p = traffic["prompt"]
    m = p.get("multiple", 1)
    first = -(-p["min"] // m) * m
    return list(range(first, p["max"] + 1, m))


def readback_keys(touched: np.ndarray, n_loaded: int, count: int,
                  seed: int) -> np.ndarray:
    """Keys read back after the store's window: up to ``count`` of the
    keys the window touched and as many keys that were never written
    (drawn above the loaded range), shuffled together."""
    rng = rng_for(seed, 3)
    touched = np.unique(np.asarray(touched, np.int64))
    hit = rng.choice(touched, min(count, len(touched)), replace=False)
    absent = n_loaded + rng.choice(n_loaded, len(hit), replace=False)
    return rng.permutation(np.concatenate([hit, absent]))


def sample_finished(requests: List[Dict], count: int, seed: int) -> List[int]:
    """Rids of the requests whose served tokens the reference checks:
    the longest one and ``count`` more drawn from the seed."""
    longest = max(requests, key=lambda r: (len(r["prompt"]) + r["max_new"],
                                           -r["rid"]))["rid"]
    others = [r["rid"] for r in requests if r["rid"] != longest]
    rng = rng_for(seed, 4)
    pick = rng.choice(others, min(count, len(others)), replace=False)
    return sorted([longest] + [int(r) for r in pick])
