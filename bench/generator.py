"""One seeded generator for every traffic mix.

A mix is a data file under ``bench/traffic/`` (``<mix>.json``).  Sizes
and arrival gaps are stratified: a block of n requests takes the n
mid-quantiles of each stated distribution, and the seed only decides the
order in which they pair and arrive and the prompt tokens.  So every
seed sends the same work in another order, and runs of different seeds
spread no more than two runs of one seed.

Keys of a mix file:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the server
  does) or ``"saturated"`` (the queue is kept ``queue_depth`` deep);
* ``rate_per_s`` (open): mean arrival rate, exponential gaps;
* ``queue_depth`` (saturated): requests kept waiting;
* ``block`` (saturated, default 64): the requests whose sizes are drawn
  together as one set of mid-quantiles; a block as large as the decode
  pool gives every seed the same sizes in the requests a window admits;
* ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``config_schedule``: ``{"period_s", "configs"}``, the engine-wide
  error config cycled through the run, switched live;
* ``drain_cap_s``: how long requests that arrived in the window may take
  to finish after it closes;
* ``sample``: ``{"max_requests", "min_tokens"}`` of the finished
  requests whose tokens are checked against the reference.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

BLOCK = 64                   # saturated mixes: default sizes per block
_STREAMS = {"requests": 1, "warmup": 2, "sample": 3}


@dataclass
class Spec:
    """One request as the generator sends it."""
    rid: int
    due_s: float             # scheduled arrival, from the window's start
    prompt: np.ndarray       # int32 token ids
    max_new: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) % (2 ** 63), _STREAMS[stream]])


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a length distribution, clipped, as ints."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.round(v), dist["min"], dist["max"]).astype(int)


def _block(mix: dict, n: int, rng: np.random.Generator):
    prompts = rng.permutation(quantile_sizes(mix["prompt_len"], n))
    outputs = rng.permutation(quantile_sizes(mix["output_len"], n))
    return prompts, outputs


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[Spec]:
    """Every request of an open-loop window, in arrival order: about
    rate * seconds arrivals, exponential gaps, all due in [0, seconds)."""
    rng = rng_for(seed, "requests")
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * seconds / gaps.sum()
    prompts, outputs = _block(mix, n, rng)
    return [Spec(i, float(due[i]),
                 rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                 int(outputs[i])) for i in range(n)]


def saturated(mix: dict, seed: int, vocab: int):
    """Endless requests for a saturated loop, due when sent."""
    rng = rng_for(seed, "requests")
    rid = 0
    while True:
        prompts, outputs = _block(mix, mix.get("block", BLOCK), rng)
        for p, o in zip(prompts, outputs):
            yield Spec(rid, 0.0, rng.integers(0, vocab, int(p),
                                              dtype=np.int32), int(o))
            rid += 1


def config_at(mix: dict, t: float) -> int:
    """The engine-wide error config the schedule holds at time t."""
    sched = mix["config_schedule"]
    return sched["configs"][int(max(t, 0.0) // sched["period_s"])
                            % len(sched["configs"])]


def configs(mix: dict) -> tuple:
    return tuple(sorted(set(mix["config_schedule"]["configs"])))
