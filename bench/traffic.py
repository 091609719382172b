"""The one traffic generator.  A traffic mix is a data file,
`bench/traffic/<name>.json`, of parameters this module reads:

  loop          "open" (requests due on a schedule, whatever the server
                does) or "closed" (a fixed number of requests
                outstanding; each completion sends the next)
  rate_per_s    open loop: mean arrival rate
  outstanding   closed loop: requests in flight
  payload_pool  distinct payloads, made once from the seed; requests
                cycle through them in a seeded order

Every seed gets the same gaps in each second of an open-loop window and
the same payloads, in another order, so seeds change the order of the
work and not its amount.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def open_schedule(traffic: Dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of every request of an
    open-loop window: round(rate * seconds) arrivals.  Each second of the
    window holds round(rate) of them, whose gaps are the exponential
    distribution's quantiles shuffled by the seed, so a seed changes the
    order of the arrivals within a second and never how many fall into
    it: a long run of short gaps, which a free shuffle of the whole
    window makes on some seeds and not others, would set the tail."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    per_s = max(1, int(round(traffic["rate_per_s"])))
    rng = _rng(seed, 1)
    gaps = []
    for start in range(0, n, per_s):
        m = min(per_s, n - start)
        g = -np.log1p(-(np.arange(m) + 0.5) / m)
        rng.shuffle(g)
        gaps.append(g)
    gaps = np.concatenate(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / gaps.sum()
    return due


def payload_order(traffic: Dict, seed: int, n: int) -> np.ndarray:
    """Pool index of each of `n` requests: every payload equally often,
    in a seeded order."""
    pool = int(traffic["payload_pool"])
    idx = np.arange(n) % pool
    _rng(seed, 2).shuffle(idx)
    return idx
