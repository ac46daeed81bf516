"""Single dispatch point: config -> (param_specs, loss/forward/decode fns).

Families:
  * LM (dense/moe/ssm/hybrid/vlm/audio-LM): models/transformer.py
  * enc-dec (seamless):                     models/encdec.py
  * clip (paper's own):                     models/clip.py
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import CLIPConfig, ModelConfig, ParallelConfig
from repro.core.precision import QuantPolicy
from repro.models import encdec as ED
from repro.models import transformer as TF
from repro.models import clip as CL
from repro.models import params as PRM


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    """Everything the trainer and server need for one architecture."""
    cfg: Any
    param_specs: Dict
    loss_fn: Callable            # (params, batch, policy, parallel) -> (loss, metrics)
    forward_fn: Callable         # prefill / plain forward
    decode_init: Callable | None
    decode_step: Callable | None


def build(cfg) -> ModelBundle:
    if isinstance(cfg, CLIPConfig):
        return ModelBundle(
            cfg=cfg,
            param_specs=CL.param_specs(cfg),
            loss_fn=lambda p, b, pol, par, **kw: CL.clip_loss(
                p, b, cfg, pol, par, **kw),
            forward_fn=lambda p, b, pol, par: CL.clip_forward(
                p, b, cfg, pol, par),
            decode_init=None,
            decode_step=None,
        )
    if cfg.family == "encdec" or cfg.encdec is not None:
        return ModelBundle(
            cfg=cfg,
            param_specs=ED.param_specs(cfg),
            loss_fn=lambda p, b, pol, par, **kw: ED.loss_fn(
                p, b, cfg, pol, par),
            forward_fn=lambda p, b, pol, par: ED.forward(p, b, cfg, pol, par),
            decode_init=lambda p, b, pol, par, batch, max_len: ED.init_decode_state(
                p, b, cfg, pol, par, batch, max_len),
            decode_step=lambda p, s, t, pol, par: ED.decode_step(
                p, s, t, cfg, pol, par),
        )
    return ModelBundle(
        cfg=cfg,
        param_specs=TF.param_specs(cfg),
        loss_fn=lambda p, b, pol, par, **kw: TF.loss_fn(p, b, cfg, pol, par),
        forward_fn=lambda p, b, pol, par: TF.forward(
            p, b["tokens"], cfg, pol, par,
            extra_embeds=b.get("extra_embeds")),
        decode_init=lambda batch, max_len: TF.init_decode_state(
            cfg, batch, max_len),
        decode_step=lambda p, s, t, pol, par: TF.decode_step(
            p, s, t, cfg, pol, par),
    )
