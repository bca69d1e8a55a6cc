"""The one traffic generator: it reads a traffic file's parameters and a
seed, and returns the work of a run.  A new mix is a new data file.

``train``: a ring of distinct token batches.  ``serve``: a cycle of
requests whose (prompt, output) lengths are a fixed grid of quantiles of
two log-normal distributions, so that every seed offers the same tokens
and the same prefill buckets; the seed decides the order, the token ids
and the arrival times.  Arrivals are a Poisson process conditioned on
the cycle holding every request of the grid exactly once: sorted uniform
times over the cycle.  The cycle is played round and round (the warm-up
plays its last seconds), so what is in flight when the window opens is
what is in flight when it closes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator per purpose, so that adding a draw to one stream
    leaves the others as they were."""
    return np.random.default_rng([int(seed), sum(stream.encode())])


def key_seed(seed: int) -> int:
    """``--seed`` may be larger than a signed 32-bit integer holds, and
    ``jax.random.key`` takes no more: fold it."""
    return int(seed) % (2 ** 31 - 1)


# ------------------------------------------------------------------ train
def train_batches(spec: dict, vocab_size: int, seed: int) -> np.ndarray:
    """(ring, batch, seq + 1) int32 tokens drawn from the seed."""
    rng = rng_for(seed, "train_tokens")
    return rng.integers(
        0, vocab_size, (spec["ring"], spec["batch"], spec["seq"] + 1),
        dtype=np.int32)


# ------------------------------------------------------------------ serve
@dataclass(frozen=True)
class Request:
    due_s: float            # seconds into the cycle
    prompt: tuple           # token ids
    max_tokens: int


def _lognormal_quantiles(dist: dict, n: int) -> List[int]:
    """n evenly spaced quantiles ((i + 1/2) / n) of a log-normal given by
    its median and sigma, clipped to [lo, hi]."""
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(dist["hi"], max(dist["lo"], round(x)))))
    return out


def length_grid(spec: dict) -> List[tuple]:
    """Every (prompt tokens, output tokens) pair of the cycle: the cross
    product of the two lists of quantiles, the same for every seed."""
    prompts = _lognormal_quantiles(spec["prompt_tokens"],
                                   spec["prompt_quantiles"])
    outputs = _lognormal_quantiles(spec["output_tokens"],
                                   spec["output_quantiles"])
    grid = [(p, o) for p in prompts for o in outputs]
    worst = max(p + o for p, o in grid)
    if worst > spec["max_context"]:
        raise ValueError(f"a pair of the traffic grid needs {worst} positions"
                         f" and max_context is {spec['max_context']}: choose "
                         "traffic on which no operation fails")
    return grid


def cycle_seconds(spec: dict, rate_scale: float = 1.0) -> float:
    return spec["cycle_seconds"] / rate_scale


def rate_rps(spec: dict, rate_scale: float = 1.0) -> float:
    """Requests offered per second: the grid, once a cycle."""
    return len(length_grid(spec)) / cycle_seconds(spec, rate_scale)


def serve_cycle(spec: dict, vocab_size: int, seed: int,
                rate_scale: float = 1.0) -> List[Request]:
    """One cycle of requests, in the order they are due."""
    grid = length_grid(spec)
    order = rng_for(seed, "serve_order").permutation(len(grid))
    period = cycle_seconds(spec, rate_scale)
    due = np.sort(rng_for(seed, "serve_arrivals").uniform(
        0.0, period, len(grid)))
    tokens = rng_for(seed, "serve_tokens")
    out = []
    for t, idx in zip(due, order):
        p, o = grid[int(idx)]
        prompt = tuple(int(x) for x in tokens.integers(0, vocab_size, p))
        out.append(Request(float(t), prompt, o))
    return out


def schedule(cycle: List[Request], period: float, warm_s: float,
             window_s: float) -> List[Request]:
    """The cycle played round and round from ``-warm_s`` to ``window_s``
    (time 0 is the start of the measured window, which starts a cycle)."""
    out = []
    first = -math.ceil(warm_s / period)
    last = math.ceil(window_s / period)
    for k in range(first, last + 1):
        for r in cycle:
            t = k * period + r.due_s
            if -warm_s <= t < window_s:
                out.append(Request(t, r.prompt, r.max_tokens))
    return out
