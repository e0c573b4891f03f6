"""Traffic from a mix file and a seed: request schedules and train batches.

The length distribution and the arrival processes are copied from the
serving simulator's workload module, so that a change there cannot move
this yardstick.  A mix file (``traffic/<mix>.json``) gives the parameters;
nothing here knows a mix by name.

Every seed gets the same amount of work.  For a serving mix, the lengths
and the inter-arrival gaps are drawn once from a fixed stream and the seed
only shuffles them (and draws the token ids), so two seeds differ in order
and content, not in load.  For a training mix, every step has the same
shape and the seed draws the tokens.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

BASE_STREAM = 20241016       # fixed: lengths and gaps are seed-independent


@dataclass(frozen=True)
class LengthDist:
    """Lognormal with a real-space mean and coefficient of variation,
    clipped to [lo, hi] and rounded (``fixed`` and ``uniform`` too)."""

    kind: str = "lognormal"
    mean: float = 512.0
    cv: float = 0.5
    lo: int = 1
    hi: int = 1 << 20

    def sample(self, rng: np.random.Generator, n: int = 1) -> np.ndarray:
        if self.kind == "fixed":
            x = np.full(n, self.mean)
        elif self.kind == "uniform":
            half = self.cv * self.mean
            x = rng.uniform(self.mean - half, self.mean + half, size=n)
        else:
            sigma2 = np.log1p(self.cv ** 2)
            mu = np.log(self.mean) - sigma2 / 2
            x = rng.lognormal(mu, np.sqrt(sigma2), size=n)
        return np.clip(np.rint(x), self.lo, self.hi).astype(np.int64)


def poisson_gaps(rng: np.random.Generator, rate: float, n: int) -> np.ndarray:
    return rng.exponential(1.0 / rate, size=n)


def bursty_times(rng: np.random.Generator, rate_low: float,
                 rate_high: float, n: int, mean_dwell: float) -> np.ndarray:
    """Two-state Markov-modulated Poisson arrivals."""
    times = np.empty(n)
    t = 0.0
    hi = False
    t_switch = rng.exponential(mean_dwell)
    for i in range(n):
        rate = rate_high if hi else rate_low
        gap = rng.exponential(1.0 / rate)
        while t + gap > t_switch:
            frac = (t_switch - t) / gap if gap > 0 else 0.0
            hi = not hi
            new_rate = rate_high if hi else rate_low
            gap = (t_switch - t) + (1 - frac) * gap * rate / new_rate
            rate = new_rate
            t_switch += rng.exponential(mean_dwell)
        t += gap
        times[i] = t
    return times


@dataclass
class Planned:
    rid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def serve_schedule(mix: Dict, seed: int, horizon_s: float,
                   vocab: int) -> List[Planned]:
    """Requests due in [0, horizon_s): lengths and gaps from the fixed
    stream, their order and the token ids from ``seed``."""
    arr = mix["arrivals"]
    base = np.random.default_rng(BASE_STREAM)
    rng = np.random.default_rng(seed)
    n = int(np.ceil(horizon_s * arr["rate"] * 1.5)) + 16
    if arr["process"] == "poisson":
        gaps = poisson_gaps(base, arr["rate"], n)
    elif arr["process"] == "bursty":
        t = bursty_times(base, arr["rate_low"], arr["rate_high"], n,
                         arr["mean_dwell_s"])
        gaps = np.diff(t, prepend=0.0)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    prompts = LengthDist(**mix["prompt"]).sample(base, n)
    outputs = LengthDist(**mix["output"]).sample(base, n)
    order = rng.permutation(n)
    if arr["process"] == "poisson":
        gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    plan = []
    for i in range(n):
        if due[i] >= horizon_s:
            break
        j = order[i]
        plan.append(Planned(i, float(due[i]),
                            rng.integers(0, vocab, size=int(prompts[j]),
                                         dtype=np.int32),
                            int(outputs[j])))
    return plan


def train_batch(mix: Dict, seed: int, index: int, batch: int,
                vocab: int) -> np.ndarray:
    """(batch, seq_len) int32 tokens: a Zipf stream in which a share
    ``structure`` of tokens follow a fixed bigram successor of the token
    before them (copied from the repo's synthetic data pipeline).  Rows of
    different ``index`` never repeat."""
    d = mix["data"]
    seq = mix["seq_len"]
    succ = np.random.default_rng(seed).integers(0, vocab, size=vocab)
    rng = np.random.default_rng([seed, index])
    base = np.clip(rng.zipf(d["zipf_a"], size=(batch, seq)) - 1, 0, vocab - 1)
    use = rng.random((batch, seq)) < d["structure"]
    toks = base.copy()
    for t in range(1, seq):
        toks[:, t] = np.where(use[:, t], succ[toks[:, t - 1]], base[:, t])
    return toks.astype(np.int32)
