"""Offline batch inference: back-to-back ``generate`` calls, each a queue of
``requests_per_call`` requests handed over at once (a closed loop: the
next call starts when the last one returns).

Prompt and output lengths are log-normal, clipped to ``[min, max]``. So
that every seed and every call carries the same work, a call's lengths
are the distribution's quantiles at ``(i + 1/2) / n`` for its ``n``
requests, paired and ordered by a permutation drawn from the seed and the
call's index. Prompt tokens are uniform over the vocabulary. Every request
is greedy and runs to its output budget (no end-of-sequence token).
"""
from __future__ import annotations

import math
import statistics
from typing import List, Tuple

import numpy as np

from bench.common import np_rng

DRIVER = "serve"


def quantile_lengths(spec, n: int) -> List[int]:
    dist = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    return [int(min(spec["max"], max(spec["min"], round(math.exp(
        dist.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def call(mix, config, seed: int, index: int) -> Tuple[List[List[int]],
                                                         List[int]]:
    """The prompts and output budgets of call ``index``."""
    n = mix["requests_per_call"]
    rng = np_rng(seed, 1000 + index)
    plens = rng.permutation(quantile_lengths(mix["prompt"], n))
    outs = rng.permutation(quantile_lengths(mix["output"], n))
    prompts = [rng.integers(0, config["vocab_size"], size=int(p),
                            dtype=np.int32).tolist() for p in plens]
    return prompts, [int(o) for o in outs]
