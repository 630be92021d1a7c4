"""The one general generator of serving traffic. A traffic mix is a data
file of parameters; this reads it.

Every seed offers the SAME multiset of (prompt length, output length)
pairs and of arrival gaps, and the same count: lengths are the quantiles
of a clipped log-normal, gaps the quantiles of an exponential (a Poisson
process's gaps), so no random generator decides how much work a window is
offered. The order is shuffled block by block, each block holding the
whole multiset, so that any stretch of ``block`` consecutive requests
carries the same work; the seed draws the token ids.

Who shuffles: with ``order_seed`` in the file, that number does, and every
seed of a run meets the same lengths and the same due times in the same
order - a window of 45 s holds fewer requests than a block, so which long
requests are in flight in it is the order's doing, and on the chip the
order moved the token rate by 1.8% and the token gap's tail by 4% where
the system's own noise is a few tenths (PERF.md, PR 24). Without the key
the run's seed shuffles.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _lognormal_quantiles(n, median, sigma, lo, hi):
    nd = NormalDist()
    return [int(min(hi, max(lo, round(
        median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def length_multiset(spec):
    """``[(prompt_len, output_len)]`` of one block: both marginals the
    quantiles of their clipped log-normals, paired by a fixed permutation
    (of the spec, not of the run's seed), prompt + output held under
    ``max_total``."""
    n = int(spec["block"])
    p, o = spec["prompt"], spec["output"]
    prompts = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                   p["max"])
    outputs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                   o["max"])
    perm = np.random.default_rng(int(spec.get("pairing_seed", 0))
                                 ).permutation(n)
    cap = int(spec["max_total"])
    return [(pl, min(outputs[j], cap - pl))
            for pl, j in zip(prompts, perm)]


def gap_multiset(spec):
    """Arrival gaps of one block in seconds: exponential quantiles at
    ``rate_per_s`` (mean exactly 1/rate); all zero for a backlog."""
    n = int(spec["block"])
    rate = float(spec.get("rate_per_s", 0.0))
    if rate <= 0.0:
        return [0.0] * n
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


def schedule(spec, seed: int, vocab: int):
    """``[{"due": s, "prompt": [ids], "max_new_tokens": n}]`` in arrival
    order, ``blocks * block`` requests."""
    rng = np.random.default_rng(seed)
    shuffler = (np.random.default_rng(int(spec["order_seed"]))
                if "order_seed" in spec else rng)
    lengths, gaps = length_multiset(spec), gap_multiset(spec)
    out, t = [], 0.0
    for _ in range(int(spec["blocks"])):
        order = shuffler.permutation(len(lengths))
        gorder = shuffler.permutation(len(gaps))
        for i, gi in zip(order, gorder):
            t += gaps[gi]
            pl, ol = lengths[i]
            out.append({"due": t, "max_new_tokens": int(ol),
                        "prompt": rng.integers(0, vocab, size=pl).tolist()})
    return out
