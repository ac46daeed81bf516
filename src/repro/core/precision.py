"""Precision policy: how every linear layer in the framework computes.

The paper's technique is integrated as a *first-class feature*: each model
config carries a ``QuantPolicy`` and every linear dispatches through
``quant_linear``. ``bf16`` is the paper's baseline; the int8/fp8 modes are
the paper's methods and baselines (see core/switchback.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import switchback as SB
from repro.core.sharding import kernel_call

Array = jax.Array

MODES = (
    "bf16", "fp16", "fp32",
    "int8", "int8_switchback", "int8_switchback_m", "int8_switchback_q",
    "int8_llm",
    "fp8_sim", "fp8_switchback", "fp8", "fp8_mixed",
)

BACKENDS = SB.BACKENDS   # ("xla", "pallas", "pallas_interpret")

_SB_VARIANT = {
    "int8": "switchback",            # alias: the knob spans int8|fp8|mixed
    "int8_switchback": "switchback",
    "int8_switchback_m": "switchback_m",
    "int8_switchback_q": "switchback_q",
    "int8_llm": "llm_int8",
    "fp8_sim": "fp8_sim",
    "fp8_switchback": "fp8_switchback",
    "fp8": "fp8",                    # real fp8 kernels (E4M3 fwd/E5M2 bwd)
    "fp8_mixed": "fp8_mixed",        # fp8 + dynamic block-level bf16 fallback
}


def variant_for_mode(mode: str) -> str:
    """The core/switchback.py variant name for a quantized policy mode."""
    return _SB_VARIANT[mode]


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Precision policy for linear layers + compute dtypes.

    mode: one of MODES. Quantized modes apply to every transformer linear
        (QKV/out projections, MLP, MoE experts, SSM in/out projections) —
        exactly the layers the paper replaces (§1: ">90% of compute").
        Embeddings, norms, routers and recurrences stay in ``compute_dtype``
        (the paper keeps "other layers, such as layer norms, in higher
        precision").
    compute_dtype: activation dtype between quantized ops.
    param_dtype: master weight dtype (f32; the optimizer sees this).
    fwd_fmt / bwd_fmt: fp8 formats for forward operands / gradients.
    backend: int8 matmul implementation for quantized modes — ``xla``
        (plain dot_general), ``pallas`` (the hand-tiled TPU kernels in
        kernels/switchback, the production hot path) or ``pallas_interpret``
        (same kernels interpreted; CPU parity testing). One config field
        flips every linear in the model between the XLA and Pallas paths.
    """
    mode: str = "bf16"
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    fwd_fmt: str = "e4m3"
    bwd_fmt: str = "e5m2"
    backend: str = "xla"
    # fp8_mixed only: blockwise-quantization tile over X/Ẏ (one scale + one
    # fallback bit per tile) and the absmax-vs-median ratio above which a
    # tile's matmul runs in bf16 (dynamic block-level fallback, DESIGN.md §13)
    fp8_block_rows: int = 128
    fp8_block_cols: int = 128
    fp8_fallback_ratio: float = 8.0

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode {self.mode!r} not in {MODES}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")

    @property
    def is_quantized(self) -> bool:
        return self.mode in _SB_VARIANT

    def with_mode(self, mode: str) -> "QuantPolicy":
        return dataclasses.replace(self, mode=mode)

    def with_backend(self, backend: str) -> "QuantPolicy":
        return dataclasses.replace(self, backend=backend)

    @classmethod
    def from_train_config(cls, tc) -> "QuantPolicy":
        """The single way launchers derive the policy from a TrainConfig:
        ``quant_mode`` + ``kernel_backend`` + the fp8 block knobs stay in
        sync by construction."""
        return cls(
            tc.quant_mode, backend=getattr(tc, "kernel_backend", "xla"),
            fp8_block_rows=getattr(tc, "fp8_block_rows", 128),
            fp8_block_cols=getattr(tc, "fp8_block_cols", 128),
            fp8_fallback_ratio=getattr(tc, "fp8_fallback_ratio", 8.0))


BF16 = QuantPolicy("bf16")
FP16 = QuantPolicy("fp16", compute_dtype=jnp.float16)
INT8_SWITCHBACK = QuantPolicy("int8_switchback")


def quant_linear(x: Array, w: Array, b: Optional[Array] = None, *,
                 policy: QuantPolicy = BF16) -> Array:
    """The single entry point for every linear layer in the framework.

    ``x``: (..., n) activations. ``w``: (n, m) master weights (param_dtype).
    Quantized modes run the SwitchBack custom-VJP; 16/32-bit modes run a
    plain dot in the compute dtype with f32 accumulation.
    """
    with jax.named_scope("linear"):
        if policy.is_quantized:
            args = (x.astype(policy.compute_dtype), w.astype(jnp.float32))
            args += () if b is None else (b,)
            sb = functools.partial(
                SB.switchback_linear,
                variant=_SB_VARIANT[policy.mode],
                fwd_fmt=policy.fwd_fmt, bwd_fmt=policy.bwd_fmt,
                backend=policy.backend,
                block_rows=policy.fp8_block_rows,
                block_cols=policy.fp8_block_cols,
                fallback_ratio=policy.fp8_fallback_ratio)
            return kernel_call(sb, *args,
                               batch_dims=(0, None, None)[:len(args)],
                               backend=policy.backend)
        cd = (jnp.float32 if policy.mode == "fp32" else policy.compute_dtype)
        y = jax.lax.dot_general(
            x.astype(cd), w.astype(cd),
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(cd)
        if b is not None:
            y = y + b.astype(y.dtype)
        return y
