"""The one general traffic generator.  A traffic mix is a data file of
parameters; this module turns it, with a seed, into requests and (for an
open loop) their due times.

Every seed sees the SAME set of (prompt length, output length) pairs, in
another order and with other token ids: lengths are the ``pool``
stratified quantiles of the file's distributions (paired by a fixed
permutation), so a seed cannot make a run lighter or heavier, only
different.  Where a window holds so few requests that their ORDER alone
moves the result (which of them straddle its edges), the file says
``"order": "fixed"``: the order then comes from the file's
``order_seed`` and the run's seed draws the token ids (and the weights)
only, so every seed does the same work in the same sequence.

Serving schema (``kind`` ``closed_loop`` | ``open_loop``):

* ``prompt_tokens`` / ``max_new_tokens``: ``{"dist": "uniform" |
  "log_uniform" | "log_normal" | "const", "lo", "hi"[, "median"]}``
* ``pool``: how many distinct length pairs (default 256)
* ``order``: ``seeded`` (default; the run's seed shuffles each pass of
  the pool) | ``fixed`` (``order_seed``, default 0, shuffles it: the same
  sequence of lengths, sessions and arrival times for every run's seed)
* ``warmup_s``: seconds of the same traffic before the window
* closed loop: ``clients`` (each submits its next request the instant
  its last one finished)
* open loop: ``rate_per_s``, ``arrival`` ``poisson`` | ``burst``; a burst
  schedule is ``burst_period_s`` on at ``burst_factor`` x the rate, then
  ``burst_period_s`` off
* ``shared_prefix_tokens`` + ``sessions``: each request belongs to one of
  ``sessions`` sessions and starts with that session's fixed prefix;
  ``prompt_tokens`` then counts the fresh tokens after it
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

POOL_PAIRING_SEED = 24  # pairs prompt and output quantiles; not the run's seed


@dataclass
class Request:
    rid: str
    prompt: List[int]
    max_new_tokens: int


def quantile(dist: dict, u: float) -> int:
    """Inverse CDF of a length distribution at ``u`` in (0, 1)."""
    kind, lo, hi = dist["dist"], dist.get("lo"), dist.get("hi")
    if kind == "const":
        return int(dist["value"])
    if kind == "uniform":
        v = lo + u * (hi - lo)
    elif kind == "log_uniform":
        v = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "log_normal":
        # hi sits two standard deviations above the median; clipped
        sigma = math.log(hi / dist["median"]) / 2.0
        v = dist["median"] * math.exp(sigma * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(v), lo), hi))


def length_pool(traffic: dict) -> List[tuple]:
    """The mix's fixed set of (prompt tokens, new tokens) pairs."""
    n = int(traffic.get("pool", 256))
    us = [(k + 0.5) / n for k in range(n)]
    pairing = np.random.default_rng(POOL_PAIRING_SEED).permutation(n)
    return [(quantile(traffic["prompt_tokens"], us[k]),
             quantile(traffic["max_new_tokens"], us[int(pairing[k])]))
            for k in range(n)]


def requests(traffic: dict, vocab: int, seed: int) -> Iterator[Request]:
    """An endless stream of requests: the pool in a seeded order, again
    and again (each pass reshuffled), token ids from the seed."""
    rng = np.random.default_rng(seed)
    order = traffic.get("order", "seeded")
    if order not in ("seeded", "fixed"):
        raise ValueError(f"unknown order {order!r}")
    # what shapes the work (order of lengths, session of a request) comes
    # from ``shape``; a seeded order draws it from the run's own stream
    shape = rng if order == "seeded" else np.random.default_rng(
        int(traffic.get("order_seed", 0)))
    pool = length_pool(traffic)
    prefix_len = int(traffic.get("shared_prefix_tokens", 0))
    sessions = int(traffic.get("sessions", 0))
    prefixes = [rng.integers(1, vocab, size=prefix_len).tolist()
                for _ in range(sessions if prefix_len else 0)]
    i = 0
    while True:
        for k in shape.permutation(len(pool)):
            n_prompt, n_new = pool[int(k)]
            prompt = rng.integers(1, vocab, size=n_prompt).tolist()
            if prefixes:
                prompt = prefixes[int(shape.integers(len(prefixes)))] + prompt
            yield Request(rid=f"r{i}", prompt=prompt, max_new_tokens=n_new)
            i += 1


def arrivals(traffic: dict, seed: int) -> Iterator[float]:
    """Open loop: seconds from the start at which each request is due
    (under ``"order": "fixed"`` the same times for every run's seed)."""
    if traffic.get("order", "seeded") == "fixed":
        seed = int(traffic.get("order_seed", 0))
    rng = np.random.default_rng(seed + 1)
    rate = float(traffic["rate_per_s"])
    kind = traffic.get("arrival", "poisson")
    t = 0.0
    if kind == "poisson":
        while True:
            t += rng.exponential(1.0 / rate)
            yield t
    elif kind == "burst":
        period = float(traffic["burst_period_s"])
        on_rate = rate * float(traffic["burst_factor"])
        while True:
            t += rng.exponential(1.0 / on_rate)
            if (t // period) % 2 == 1:  # landed in an off period: skip it
                t = (t // period + 1) * period
            yield t
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
