"""SwitchBack: a linear layer for int8/fp8 quantized *training* (paper §2.2).

The layer performs three matmuls:

    forward:    Y  = X  W      (X: (b, n),  W: (n, m),  Y: (b, m))
    input grad: Ẋ  = Ẏ  Wᵀ     (inner dim m — small, a multiple of embed dim)
    weight grad:Ẇ  = Xᵀ Ẏ      (inner dim b = batch*seq — HUGE for CLIP)

SwitchBack's insight (paper App. C): quantization variance grows linearly
with the matmul inner dimension, so the weight-gradient matmul — whose inner
dim is batch×seq — must stay in 16-bit, while the other two run in 8-bit.

Variants (all released by the paper, all implemented here):

* ``switchback``   (Alg. 1): row-wise X/Ẏ, tensor-wise W; residuals saved in
                   the input dtype.
* ``switchback_m`` (Alg. 3): memory-efficient — saves only the *int8* X and
                   its state; X is dequantized on the backward pass before
                   the 16-bit weight-grad matmul (small extra dequant cost,
                   ~4x activation-memory saving).
* ``switchback_q`` (Alg. 4): row-/column-wise W quantization instead of
                   tensor-wise.
* ``llm_int8``     LLM.int8()-style baseline: all *three* matmuls int8 with
                   row/column-wise quantization — the paper's failing
                   baseline (5.9pp drop at ViT-Huge), kept for comparison.
* ``fp8_sim``      the paper's fp8 baseline: tensor-wise fp8 for inputs,
                   weights and grads in all three matmuls (diverges at scale
                   unless zero-init layer-scale is used, §2.3).
* ``fp8_switchback``: SwitchBack with fp8 quantizers (row-wise E4M3 inputs,
                   tensor-wise E4M3 weights, row-wise E5M2 grads, bf16 wgrad).
* ``fp8``         real fp8 execution (not simulation): row-wise E4M3 X,
                   tensor-wise E4M3 W, row-wise E5M2 Ẏ dgrad, bf16 wgrad —
                   all through the kernels/fp8_matmul tiled kernels with
                   Scalify-style explicit scales (DESIGN.md §13).
* ``fp8_mixed``   fp8 with dynamic block-level bf16 fallback: X and Ẏ are
                   quantized in (block_rows × block_cols) tiles; tiles whose
                   absmax exceeds ``fallback_ratio`` × the median run the
                   matmul tile in bf16 against the dequantized weight
                   ("Accurate INT8 Training Through Dynamic Block-Level
                   Fallback" applied to fp8).

Note on the GPU→TPU adaptation: the paper fuses a transpose into the weight
quantizer (``tensor-wise_quantize_transpose``) because cuBLAS int8 only
implements ABᵀ.  The TPU MXU contracts arbitrary dimension pairs through
``lax.dot_general`` dimension numbers, so no transpose is ever materialized
here — see DESIGN.md §3.

Backends: every int8 variant can route its forward and input-grad (dgrad)
matmuls through the hand-tiled Pallas kernels in ``kernels/switchback``:

* ``xla``              (default) plain ``lax.dot_general`` — what the XLA
                       compiler does with the int8 dots on its own.
* ``pallas``           the compiled Pallas TPU kernels (fused quantize /
                       dequant epilogues, DESIGN.md §3) — the hot path.
* ``pallas_interpret`` the same kernels in interpret mode — runs anywhere
                       (CPU), used by the parity tests.

The 16-bit weight-grad matmul always stays on ``dot_general``: it is the
paper's "switch back" and XLA already emits an optimal bf16 MXU matmul for
it.  The ``fp8_sim``/``fp8_switchback`` variants are simulation-only (no
kernels) and ignore the backend knob; ``fp8``/``fp8_mixed`` dispatch on it
through kernels/fp8_matmul exactly as the int8 variants do.
"""
from __future__ import annotations

import functools
from typing import Literal, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantization as Q
from repro.kernels.fp8_matmul import ops as F8OPS
from repro.kernels.switchback import ops as KOPS

Array = jax.Array
Variant = Literal[
    "switchback", "switchback_m", "switchback_q", "llm_int8",
    "fp8_sim", "fp8_switchback", "fp8", "fp8_mixed",
]

VARIANTS: Tuple[str, ...] = (
    "switchback", "switchback_m", "switchback_q", "llm_int8",
    "fp8_sim", "fp8_switchback", "fp8", "fp8_mixed",
)

# simulation-only fp8 variants: quantize-dequantize in the model graph,
# backend knob ignored (kernels would buy nothing — the dots are bf16/f32)
SIM_FP8_VARIANTS: Tuple[str, ...] = ("fp8_sim", "fp8_switchback")

BACKENDS: Tuple[str, ...] = KOPS.BACKENDS


# ---------------------------------------------------------------------------
# int8 contraction helpers (w stored (n_in, m_out), jnp convention)
# ---------------------------------------------------------------------------

def _dot_i8(a: Array, b: Array, contract: Tuple[int, int]) -> Array:
    """int8 x int8 -> int32 contraction. On TPU this hits the MXU int8 path
    (2x bf16 throughput); the Pallas kernel in kernels/switchback is the
    hand-tiled equivalent."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _dot_f32(a: Array, b: Array, contract: Tuple[int, int]) -> Array:
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


_I2 = Q.INT8_QMAX * Q.INT8_QMAX


def _fwd_int8_rowwise_tensorwise(x: Array, w: Array, out_dtype):
    """Eq. (3) forward: Y = (s_w/127² · s_x) ⊙ (Q_row(X) Q_tensor(W))."""
    x_q, s_x = Q.quantize_rowwise(x)            # (b, n), (b, 1)
    w_q, s_w = Q.quantize_tensorwise(w)         # (n, m), scalar
    acc = _dot_i8(x_q, w_q, (1, 0))             # (b, m) int32
    y = acc.astype(jnp.float32) * (s_x * (s_w / _I2))
    return y.astype(out_dtype), (x_q, s_x, w_q, s_w)


def _fwd_int8_rowwise_colwise(x: Array, w: Array, out_dtype):
    """Eq. (4) forward (SwitchBackQ / LLM.int8): per-output-unit W scales."""
    x_q, s_x = Q.quantize_rowwise(x)            # (b, n), (b, 1)
    w_q, s_w = Q.quantize_columnwise(w)         # (n, m), (1, m)
    acc = _dot_i8(x_q, w_q, (1, 0))             # (b, m)
    y = acc.astype(jnp.float32) * (s_x * (s_w / _I2))
    return y.astype(out_dtype), (x_q, s_x, w_q, s_w)


def _dgrad_int8(g: Array, w_q: Array, s_g: Array, s_w, out_dtype):
    """Ẋ = Ẏ Wᵀ in int8: contract over m (w_q dim 1). ``s_w`` scalar
    (tensor-wise) or (1, m) — for the (1, m) case the scale does not factor
    out of the contraction, so callers must pre-fold it into g (see below)."""
    acc = _dot_i8(g, w_q, (1, 1))               # (b, n)
    dx = acc.astype(jnp.float32) * (s_g * (s_w / _I2))
    return dx.astype(out_dtype)


def _wgrad_16bit(x: Array, g: Array) -> Array:
    """Ẇ = Xᵀ Ẏ in 16-bit inputs / f32 accumulation — the SwitchBack "switch
    back". Inner dim is b = batch*seq; App. C shows int8 noise here scales
    with b and destroys training."""
    return _dot_f32(x.astype(jnp.bfloat16), g.astype(jnp.bfloat16), (0, 0))


def _wgrad_int8(x: Array, g: Array) -> Array:
    """LLM.int8() weight grad: Ẇ[n,m] = Σ_b X[b,n] Ẏ[b,m] with X quantized
    per-column-of-X (= per n, state (1,n)) and Ẏ per-column (= per m).
    This is the matmul SwitchBack refuses to quantize."""
    x_q, s_x = Q.quantize_columnwise(x)         # (b, n), (1, n)
    g_q, s_g = Q.quantize_columnwise(g)         # (b, m), (1, m)
    acc = _dot_i8(x_q, g_q, (0, 0))             # (n, m)
    dw = acc.astype(jnp.float32) * (s_x.T * (s_g / _I2))
    return dw


# Pallas-kernel equivalents (kernels/switchback/ops.py dispatchers) ---------

def _kfwd_rowwise_tensorwise(x: Array, w: Array, out_dtype, backend: str):
    """Eq. (3) forward on the Pallas path. Uses the single-HBM-pass fused
    quantize+matmul kernel when K fits one VMEM block, else the two-step
    row-quantize → tiled-matmul pipeline (same math, DESIGN.md §3)."""
    w_q, s_w = KOPS.tensor_quantize(w, backend=backend)      # (n, m), (1, 1)
    if x.shape[1] <= KOPS.FUSED_MAX_CONTRACT:
        y = KOPS.fused_switchback_fwd(x, w_q, s_w, out_dtype=out_dtype,
                                      backend=backend)
    else:
        x_q, s_x = KOPS.row_quantize(x, backend=backend)
        y = KOPS.int8_matmul_dequant(x_q, w_q, s_x * (s_w / _I2),
                                     out_dtype=out_dtype, backend=backend)
    return y, w_q, s_w


def _kdgrad_tensorwise(g: Array, w_q: Array, s_w: Array, out_dtype,
                       backend: str):
    """Ẋ = Ẏ Wᵀ on the Pallas path: fused Ẏ-quantize dgrad kernel when the
    contraction dim m fits one VMEM block, else two-step. ``w_q`` is the
    forward's int8 W, contracted over its second dim — never transposed."""
    if g.shape[1] <= KOPS.FUSED_MAX_CONTRACT:
        return KOPS.fused_switchback_dgrad(g, w_q, s_w, out_dtype=out_dtype,
                                           backend=backend)
    g_q, s_g = KOPS.row_quantize(g, backend=backend)
    return KOPS.int8_matmul_dequant(g_q, w_q, s_g * (s_w / _I2),
                                    transpose_w=True, out_dtype=out_dtype,
                                    backend=backend)


# fp8 equivalents -----------------------------------------------------------

def _fwd_fp8_tensorwise(x: Array, w: Array, out_dtype, fwd_fmt: str):
    x_q, s_x = Q.quantize_tensorwise_fp8(x, fwd_fmt)
    w_q, s_w = Q.quantize_tensorwise_fp8(w, fwd_fmt)
    acc = _dot_f32(x_q, w_q, (1, 0))
    y = acc * (s_x * s_w)
    return y.astype(out_dtype), (x_q, s_x, w_q, s_w)


def _fwd_fp8_rowwise_tensorwise(x: Array, w: Array, out_dtype, fwd_fmt: str):
    x_q, s_x = Q.quantize_rowwise_fp8(x, fwd_fmt)
    w_q, s_w = Q.quantize_tensorwise_fp8(w, fwd_fmt)
    acc = _dot_f32(x_q, w_q, (1, 0))
    y = acc * (s_x * s_w)
    return y.astype(out_dtype), (x_q, s_x, w_q, s_w)


# ---------------------------------------------------------------------------
# custom_vjp assembly
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def make_switchback_matmul(variant: str = "switchback",
                           fwd_fmt: str = "e4m3",
                           bwd_fmt: str = "e5m2",
                           backend: str = "xla",
                           block_rows: int = 128,
                           block_cols: int = 128,
                           fallback_ratio: float = 8.0):
    """Build the custom-VJP 2-D matmul ``f(x2d, w) -> y2d`` for a variant.

    x2d: (b, n) activations (b = flattened batch*seq), w: (n, m) weights.
    Gradients: dx in x.dtype, dw in f32 (master-weight precision).

    ``backend`` routes the int8 and real-fp8 forward/dgrad matmuls: ``xla``
    (the pure-jnp oracles), ``pallas`` (the fused TPU kernels) or
    ``pallas_interpret`` (same kernels, interpreter — CPU-testable). The
    16-bit weight-grad and the simulated fp8 variants always use
    ``dot_general``.

    ``block_rows``/``block_cols``/``fallback_ratio`` apply to ``fp8_mixed``
    only: the blockwise-quantization tile shape over X/Ẏ and the
    outlier-vs-median absmax ratio above which a tile falls back to bf16.
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown SwitchBack variant {variant!r}; "
                         f"expected one of {VARIANTS}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    use_kernels = backend != "xla" and not variant.startswith("fp8")

    # ---------------- forward implementations -----------------------------
    # The variant is static (factory closure), so residuals are pure arrays.
    def fwd(x, w):
        odt = x.dtype
        if variant == "switchback":
            if use_kernels:
                y, w_q, s_w = _kfwd_rowwise_tensorwise(x, w, odt, backend)
            else:
                y, (x_q, s_x, w_q, s_w) = _fwd_int8_rowwise_tensorwise(
                    x, w, odt)
            res = (x, w_q, s_w)                       # fp X + int8 W
        elif variant == "switchback_m":
            if use_kernels:
                x_q, s_x = KOPS.row_quantize(x, backend=backend)
                w_q, s_w = KOPS.tensor_quantize(w, backend=backend)
                y = KOPS.int8_matmul_dequant(
                    x_q, w_q, s_x * (s_w / _I2), out_dtype=odt,
                    backend=backend)
            else:
                y, (x_q, s_x, w_q, s_w) = _fwd_int8_rowwise_tensorwise(
                    x, w, odt)
            res = (x_q, s_x, w_q, s_w)                # int8 residuals only
        elif variant in ("switchback_q", "llm_int8"):
            if use_kernels:
                x_q, s_x = KOPS.row_quantize(x, backend=backend)
                w_q, s_w = KOPS.col_quantize(w, backend=backend)  # (1, m)
                y = KOPS.int8_matmul_dequant(
                    x_q, w_q, s_x / _I2, col_scale=s_w, out_dtype=odt,
                    backend=backend)
            else:
                y, _ = _fwd_int8_rowwise_colwise(x, w, odt)
            res = (x, w)                              # re-quantize W in bwd
        elif variant == "fp8_sim":
            y, _ = _fwd_fp8_tensorwise(x, w, odt, fwd_fmt)
            res = (x, w)
        elif variant == "fp8_switchback":
            y, (x_q, s_x, w_q, s_w) = _fwd_fp8_rowwise_tensorwise(
                x, w, odt, fwd_fmt)
            res = (x, w_q, s_w)
        elif variant == "fp8":
            # real fp8 execution: row-wise E4M3 X, tensor-wise E4M3 W,
            # Scalify-style explicit scales folded into one (b, 1) multiply
            w_q, s_w = F8OPS.tensor_quantize(w, fmt=fwd_fmt, backend=backend)
            x_q, s_x = F8OPS.row_quantize(x, fmt=fwd_fmt, backend=backend)
            y = F8OPS.fp8_matmul_dequant(x_q, w_q, s_x * s_w, out_dtype=odt,
                                         backend=backend)
            res = (x, w_q, s_w)                       # fp X + fp8 W
        elif variant == "fp8_mixed":
            w_q, s_w = F8OPS.tensor_quantize(w, fmt=fwd_fmt, backend=backend)
            y = F8OPS.fp8_mixed_matmul(
                x, w_q, s_w, fmt=fwd_fmt, block_rows=block_rows,
                block_cols=block_cols, fallback_ratio=fallback_ratio,
                out_dtype=odt, backend=backend)
            res = (x, w_q, s_w)
        return y, res

    # ---------------- backward implementations ----------------------------
    def bwd(res, g):
        odt = g.dtype

        if variant in ("switchback", "switchback_m"):
            # dX: int8 (row-wise g, tensor-wise w). dW: 16-bit.
            if variant == "switchback":
                x, w_q, s_w = res
            else:
                x_q, s_x, w_q, s_w = res
                x = Q.dequantize_rowwise(x_q, s_x, jnp.bfloat16)  # extra dequant (Alg. 3)
            if use_kernels:
                dx = _kdgrad_tensorwise(g, w_q, s_w, odt, backend)
            else:
                g_q, s_g = Q.quantize_rowwise(g)
                dx = _dgrad_int8(g_q, w_q, s_g, s_w, odt)
            dw = _wgrad_16bit(x, g)
            return dx, dw

        if variant in ("switchback_q", "llm_int8"):
            x, w = res
            # column-wise W state (1, m) sits on the *contracted* dim of the
            # dgrad matmul, so it cannot be folded out — quantize W row-wise
            # along n instead (paper Alg. 4: column-wise_quantize_transpose,
            # i.e. per-n scales after transposition; identical semantics).
            if use_kernels:
                g_q, s_g = KOPS.row_quantize(g, backend=backend)
                w_q_n, s_w_n = KOPS.row_quantize(w, backend=backend)
                dx = KOPS.int8_matmul_dequant(
                    g_q, w_q_n, s_g / _I2, col_scale=s_w_n.T,
                    transpose_w=True, out_dtype=odt, backend=backend)
            else:
                g_q, s_g = Q.quantize_rowwise(g)
                w_q_n, s_w_n = Q.quantize_rowwise(w)  # (n, m), state (n, 1)
                acc = _dot_i8(g_q, w_q_n, (1, 1))     # (b, n)
                dx = (acc.astype(jnp.float32)
                      * (s_g * (s_w_n.T / _I2))).astype(odt)
            if variant == "llm_int8":
                dw = _wgrad_int8(x, g)                # the fatal int8 wgrad
            else:
                dw = _wgrad_16bit(x, g)               # switchback_q
            return dx, dw

        if variant == "fp8_sim":
            x, w = res
            # everything tensor-wise fp8, grads in the gradient format
            g_q, s_g = Q.quantize_tensorwise_fp8(g, bwd_fmt)
            w_q, s_w = Q.quantize_tensorwise_fp8(w, fwd_fmt)
            dx = (_dot_f32(g_q, w_q, (1, 1)) * (s_g * s_w)).astype(odt)
            x_q, s_x = Q.quantize_tensorwise_fp8(x, fwd_fmt)
            dw = _dot_f32(x_q, g_q, (0, 0)) * (s_x * s_g)
            return dx, dw

        if variant == "fp8_switchback":
            x, w_q, s_w = res
            g_q, s_g = Q.quantize_rowwise_fp8(g, bwd_fmt)
            dx = (_dot_f32(g_q, w_q, (1, 1)) * (s_g * s_w)).astype(odt)
            dw = _wgrad_16bit(x, g)
            return dx, dw

        if variant == "fp8":
            # dgrad in the gradient format (E5M2: more exponent range for
            # grads), reusing the forward's fp8 W — contracted over its
            # second dim, never transposed; wgrad switches back to 16-bit
            x, w_q, s_w = res
            g_q, s_g = F8OPS.row_quantize(g, fmt=bwd_fmt, backend=backend)
            dx = F8OPS.fp8_matmul_dequant(g_q, w_q, s_g * s_w,
                                          transpose_w=True, out_dtype=odt,
                                          backend=backend)
            dw = _wgrad_16bit(x, g)
            return dx, dw

        if variant == "fp8_mixed":
            x, w_q, s_w = res
            dx = F8OPS.fp8_mixed_matmul(
                g, w_q, s_w, fmt=bwd_fmt, block_rows=block_rows,
                block_cols=block_cols, fallback_ratio=fallback_ratio,
                transpose_w=True, out_dtype=odt, backend=backend)
            dw = _wgrad_16bit(x, g)
            return dx, dw

        raise AssertionError(variant)

    @jax.custom_vjp
    def switchback_matmul(x, w):
        y, _ = fwd(x, w)
        return y

    switchback_matmul.defvjp(fwd, bwd)
    return switchback_matmul


def switchback_linear(x: Array, w: Array, b: Array | None = None, *,
                      variant: str = "switchback",
                      fwd_fmt: str = "e4m3", bwd_fmt: str = "e5m2",
                      backend: str = "xla",
                      block_rows: int = 128, block_cols: int = 128,
                      fallback_ratio: float = 8.0) -> Array:
    """Apply a SwitchBack linear to ``x`` of shape (..., n) with ``w`` of
    shape (n, m). Leading dims are flattened for the 2-D quantized matmul
    (row-wise state = one scale per token, as in the paper) and restored.
    ``backend`` selects the quantized matmul implementation; the block
    knobs parameterize ``fp8_mixed`` fallback (module docstring)."""
    n = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape((-1, n))
    f = make_switchback_matmul(variant, fwd_fmt, bwd_fmt, backend,
                               block_rows, block_cols, fallback_ratio)
    y2 = f(x2, w)
    y = y2.reshape(lead + (w.shape[-1],))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# grouped SwitchBack: the routed experts of a dropless MoE layer
# ---------------------------------------------------------------------------

def quantize_per_expert(w: Array) -> Tuple[Array, Array]:
    """w (E, n, m) -> (int8 (E, n, m), absmax (E, 1, 1) f32): each expert's
    weight tensor-wise, as SwitchBack quantizes one linear's."""
    wf = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(wf), axis=(1, 2), keepdims=True), 1e-12)
    return jnp.round(wf * (Q.INT8_QMAX / s)).astype(jnp.int8), s


@functools.lru_cache(maxsize=None)
def make_grouped_switchback(backend: str = "xla", block_m: int = 256):
    """``f(x, w, tile_expert, n_live) -> y``: SwitchBack (Alg. 1) for the
    rows of every expert at once. x (R, n) holds each expert's slots in
    whole ``block_m``-row tiles, tile i of expert ``tile_expert[i]``;
    ``n_live`` (1,) counts the tiles that hold slots. Forward and input
    gradient run int8 (rows per row, each expert's weight per tensor), the
    weight gradient (E, n, m) in bf16 with f32 accumulation, through the
    ``moe_gmm_*`` kernels on the Pallas backends. Rows of tiles past
    ``n_live`` come out undefined in y and dx; the caller masks them."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")

    def fwd(x, w, tile_expert, n_live):
        w_q, s_w = quantize_per_expert(w)
        y = KOPS.moe_gmm_fwd(x, w_q, s_w, tile_expert, n_live,
                             block_m=block_m, out_dtype=x.dtype,
                             backend=backend)
        return y, (x, w_q, s_w, tile_expert, n_live)

    def bwd(res, g):
        x, w_q, s_w, tile_expert, n_live = res
        dx = KOPS.moe_gmm_dgrad(g, w_q, s_w, tile_expert, n_live,
                                block_m=block_m, out_dtype=x.dtype,
                                backend=backend)
        dw = KOPS.moe_gmm_wgrad(x, g, tile_expert, n_live, block_m=block_m,
                                n_experts=w_q.shape[0], backend=backend)
        return dx, dw, None, None

    @jax.custom_vjp
    def grouped(x, w, tile_expert, n_live):
        return fwd(x, w, tile_expert, n_live)[0]

    grouped.defvjp(fwd, bwd)
    return grouped
