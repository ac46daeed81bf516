"""CLIP training batches: ``batch`` image-text pairs, images of the
configuration's size with standard-normal pixels (what normalised images
look like to the model) and captions of the full context of token ids
uniform over the vocabulary. ``ring`` distinct batches are made on the
host from the seed; the run cycles through them and hands each step its
batch from the host."""
from __future__ import annotations

import numpy as np

from bench.common import np_rng

DRIVER = "train"


def make(mix, config, seed: int):
    rng = np_rng(seed, 1)
    v, t = config["vision_cfg"], config["text_cfg"]
    B, px = mix["batch"], v["image_size"]
    out = []
    for _ in range(mix["ring"]):
        out.append({
            "images": rng.standard_normal((B, px, px, 3), dtype=np.float32),
            "texts": rng.integers(1, t["vocab_size"],
                                  size=(B, t["context_length"]),
                                  dtype=np.int32)})
    return out

