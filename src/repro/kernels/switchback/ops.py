"""Jit'd public wrappers for the SwitchBack kernels.

Handles: backend dispatch (pallas TPU / pallas interpret / pure-XLA ref),
shape padding to block multiples, and the Triton-autotune→static-heuristic
block-size choice (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.switchback import ref as _ref
from repro.kernels.switchback import switchback as _k

Backend = Literal["xla", "pallas", "pallas_interpret"]
BACKENDS: tuple[str, ...] = ("xla", "pallas", "pallas_interpret")

# v5e VMEM is ~16 MiB; leave headroom for double-buffering (Pallas pipelines
# two blocks per operand) and semaphores.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# The fused quantize+matmul kernels keep the whole contraction dim in one
# VMEM block; above this the two-step quantize→tiled-matmul path wins
# (DESIGN.md §3).
FUSED_MAX_CONTRACT = 2048

# Tensor-wise quantize streams full-width row blocks; each input block is
# kept near this size so its double-buffered pipeline fits v5e's 16 MiB
# scoped VMEM (a 512-row f32 block of a 5120-wide weight needs 20 MiB).
QUANT_BLOCK_BYTES = 2 * 1024 * 1024


def quant_block_rows(x: jax.Array, block_bytes: int = QUANT_BLOCK_BYTES
                     ) -> int:
    """Rows per tensor-quantize block: at most 512 and about
    ``block_bytes`` of input, a multiple of 32 (int8 sublane packing) —
    or all of x's rows when fewer."""
    R, C = x.shape
    rows = block_bytes // (C * x.dtype.itemsize) // 32 * 32
    return min(max(rows, 32), 512, R)


def choose_blocks(B: int, K: int, M: int) -> tuple[int, int, int]:
    """Static replacement for Triton autotune: largest MXU-aligned tiles
    whose double-buffered working set fits the VMEM budget.

    Working set per grid step (int8 matmul):
        2·(bb·bk) int8  +  2·(bk·bm) int8  +  bb·bm·4 acc  +  bb·bm·out
    Preference order: grow bk (fewer accumulation passes over the output),
    then bm, then bb — matching the paper's observation that speedup grows
    with dim.
    """
    def fits(bb, bk, bm):
        ws = 2 * bb * bk + 2 * bk * bm + bb * bm * 4 + bb * bm * 2
        return ws <= VMEM_BUDGET_BYTES

    bb, bm, bk = 256, 256, 512
    while bk * 2 <= min(K, 4096) and fits(bb, bk * 2, bm):
        bk *= 2
    while bm * 2 <= min(M, 1024) and fits(bb, bk, bm * 2):
        bm *= 2
    while bb * 2 <= min(B, 1024) and fits(bb * 2, bk, bm):
        bb *= 2
    return bb, bk, bm


def _pad_to(x: jax.Array, mult: tuple[int, int]) -> jax.Array:
    pb = (-x.shape[0]) % mult[0]
    pk = (-x.shape[1]) % mult[1]
    if pb or pk:
        x = jnp.pad(x, ((0, pb), (0, pk)))
    return x


@functools.partial(jax.jit, static_argnames=("backend",))
def row_quantize(x: jax.Array, backend: Backend = "xla"):
    """x (B, K) -> (q int8 (B, K), state f32 (B, 1))."""
    if backend == "xla":
        return _ref.row_quantize(x)
    interp = backend == "pallas_interpret"
    B = x.shape[0]
    bb = 256 if B >= 256 else B
    xp = _pad_to(x, (bb, 1))
    q, s = _k.row_quantize(xp, block_b=bb, interpret=interp)
    return q[:B], s[:B]


@functools.partial(jax.jit, static_argnames=("backend",))
def col_quantize(x: jax.Array, backend: Backend = "xla"):
    """x (R, C) -> (q int8 (R, C), state f32 (1, C)): per-column scales
    (SwitchBackQ / LLM.int8 weight quantization, paper Eq. 4)."""
    if backend == "xla":
        return _ref.col_quantize(x)
    interp = backend == "pallas_interpret"
    C = x.shape[1]
    bc = 256 if C >= 256 else C
    xp = _pad_to(x, (1, bc))   # zero cols: scale floors at 1e-12, sliced off
    q, s = _k.col_quantize(xp, block_c=bc, interpret=interp)
    return q[:, :C], s[:, :C]


@functools.partial(jax.jit, static_argnames=("backend",))
def tensor_quantize(x: jax.Array, backend: Backend = "xla"):
    if backend == "xla":
        return _ref.tensor_quantize(x)
    interp = backend == "pallas_interpret"
    R = x.shape[0]
    br = quant_block_rows(x)
    xp = _pad_to(x, (br, 1))   # zero rows don't change the absmax
    q, s = _k.tensor_quantize(xp, block_rows=br, interpret=interp)
    return q[:R], s


@functools.partial(jax.jit, static_argnames=("transpose_w", "out_dtype", "backend"))
def int8_matmul_dequant(x_q, w_q, row_scale, *, col_scale=None,
                        transpose_w=False, out_dtype=jnp.bfloat16,
                        backend: Backend = "xla"):
    """y = row_scale ⊙ (x_q · w_q[ᵀ]) [⊙ col_scale] with int32 accumulation.

    `row_scale` is (B, 1) f32 and already folds the weight scale
    (s_x · s_w/127²) so the epilogue is a single broadcast multiply.
    With column-wise weight states (paper Eq. 4) pass the (1, M) scale as
    `col_scale` instead — the epilogue becomes a rank-1 scale.
    """
    if backend == "xla":
        return _ref.int8_matmul_dequant(
            x_q, w_q, row_scale, col_scale=col_scale,
            transpose_w=transpose_w, out_dtype=out_dtype)
    interp = backend == "pallas_interpret"
    B, K = x_q.shape
    M = w_q.shape[0] if transpose_w else w_q.shape[1]
    bb, bk, bm = choose_blocks(B, K, M)
    xp = _pad_to(x_q, (bb, bk))
    wp = _pad_to(w_q, (bm, bk) if transpose_w else (bk, bm))
    sp = _pad_to(row_scale, (bb, 1))
    cp = None if col_scale is None else _pad_to(col_scale, (1, bm))
    y = _k.int8_matmul_dequant(
        xp, wp, sp, col_scale=cp, transpose_w=transpose_w,
        out_dtype=out_dtype, block_b=bb, block_m=bm, block_k=bk,
        interpret=interp)
    return y[:B, :M]


@functools.partial(jax.jit, static_argnames=("out_dtype", "backend"))
def fused_switchback_fwd(x, w_q, s_w, *, out_dtype=jnp.bfloat16,
                         backend: Backend = "xla"):
    """Forward SwitchBack with fused X row-quantize (K in one VMEM block)."""
    if backend == "xla":
        return _ref.fused_switchback_fwd(x, w_q, s_w, out_dtype=out_dtype)
    interp = backend == "pallas_interpret"
    B, K = x.shape
    M = w_q.shape[1]
    bb = min(256, B)
    bm = min(512, M)
    xp = _pad_to(x, (bb, 1))
    wp = _pad_to(w_q, (1, bm))
    y = _k.fused_switchback_fwd(xp, wp, s_w, out_dtype=out_dtype,
                                block_b=bb, block_m=bm, interpret=interp)
    return y[:B, :M]


@functools.partial(jax.jit, static_argnames=("out_dtype", "backend"))
def fused_switchback_dgrad(g, w_q, s_w, *, out_dtype=jnp.bfloat16,
                           backend: Backend = "xla"):
    """Input-grad SwitchBack with fused Ẏ row-quantize (M in one VMEM
    block): dx = s_g ⊙ (Q_row(Ẏ) · Wᵢ₈ᵀ) · s_w/127², contracting over m via
    dimension numbers — W stays (n, m) as the forward quantized it."""
    if backend == "xla":
        return _ref.fused_switchback_dgrad(g, w_q, s_w, out_dtype=out_dtype)
    interp = backend == "pallas_interpret"
    B, M = g.shape
    N = w_q.shape[0]
    bb = min(256, B)
    bn = min(512, N)
    gp = _pad_to(g, (bb, 1))
    wp = _pad_to(w_q, (bn, 1))
    dx = _k.fused_switchback_dgrad(gp, wp, s_w, out_dtype=out_dtype,
                                   block_b=bb, block_n=bn, interpret=interp)
    return dx[:B, :N]


@functools.partial(jax.jit, static_argnames=("backend",))
def wgrad_bf16(x, g, backend: Backend = "xla"):
    """Ẇ = Xᵀ Ẏ in bf16/f32 — the 16-bit 'switch back' matmul."""
    if backend == "xla":
        return _ref.wgrad_bf16(x, g)
    interp = backend == "pallas_interpret"
    B, K = x.shape
    M = g.shape[1]
    bb = min(512, B)
    bk = min(256, K)
    bm = min(256, M)
    xp = _pad_to(x, (bb, bk))
    gp = _pad_to(g, (bb, bm))
    y = _k.wgrad_bf16(xp, gp, block_k=bk, block_m=bm, block_b=bb,
                      interpret=interp)
    return y[:K, :M]
