"""Language-model training batches: ``batch`` rows of ``seq`` token ids,
uniform over the configuration's vocabulary, with their next tokens as
labels. ``ring`` distinct batches are made on the host from the seed; the
run cycles through them and hands each step its batch from the host."""
from __future__ import annotations

import numpy as np

from bench.common import np_rng

DRIVER = "train"


def make(mix, config, seed: int):
    rng = np_rng(seed, 1)
    B, S, V = mix["batch"], mix["seq"], config["vocab_size"]
    out = []
    for _ in range(mix["ring"]):
        ids = rng.integers(0, V, size=(B, S + 1), dtype=np.int32)
        out.append({"tokens": ids[:, :-1], "labels": ids[:, 1:]})
    return out

