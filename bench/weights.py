"""Random weights from the seed, made by the benchmark and not by the
program, so that the reference can make the same ones.

The tree's layout (names and shapes) is the program's parameter tree; the
values follow one rule by leaf name: norm gains 1, biases 0, token and
positional embeddings and class tokens N(0, 0.02) (text positions 0.01,
as OpenCLIP), the CLIP logit scale ln(1/0.07), and every other matrix
N(0, 1/fan_in) with fan_in its second-to-last dimension.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ZERO = {"bias", "bq", "bk", "bv", "bo", "b_up", "b_down"}


def _names(path):
    return [getattr(k, "key", getattr(k, "name", str(k))) for k in path]


def leaf_init(names, shape, key):
    """The value of one leaf, from its path names and shape."""
    name = names[-1]
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name in ZERO:
        return jnp.zeros(shape, jnp.float32)
    if name == "logit_scale":
        return jnp.full(shape, math.log(1 / 0.07), jnp.float32)
    if name == "pos_embed" and "text" in names:
        std = 0.01
    elif name in ("embed", "pos_embed", "cls_token"):
        std = 0.02
    else:
        std = 1.0 / math.sqrt(shape[-2])
    return std * jax.random.normal(key, shape, jnp.float32)


def make(abstract, key):
    """Weights for the tree of ShapeDtypeStructs ``abstract`` (traceable:
    call it inside one jit so that they are born on the device)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.random.split(key, len(flat))
    return treedef.unflatten([
        leaf_init(_names(p), a.shape, k).astype(a.dtype)
        for (p, a), k in zip(flat, keys)])
