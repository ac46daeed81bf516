"""Transformer MLP (SwiGLU / GELU) through the precision policy."""
from __future__ import annotations

import jax

from repro.core.precision import QuantPolicy, quant_linear
from repro.models.common import activation

Array = jax.Array


def mlp_block(x: Array, p: dict, cfg, policy: QuantPolicy) -> Array:
    """x: (B, S, D) -> (B, S, D). SwiGLU uses w_gate; GELU does not."""
    cd = policy.compute_dtype
    h = quant_linear(x, p["w_up"].astype(cd), policy=policy)
    g = (quant_linear(x, p["w_gate"].astype(cd), policy=policy)
         if "w_gate" in p else None)
    h = activation(h, g, cfg.act)
    return quant_linear(h, p["w_down"].astype(cd), policy=policy)
