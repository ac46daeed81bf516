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

from repro.kernels.switchback import grouped as _g
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


# Row-wise quantize holds whole rows: a block's input, int8 output (both
# double-buffered) and f32 temporaries take about 6 bytes per element of
# scoped VMEM (256 rows of 11264 asked 16.5 MiB of the default 16).
ROW_QUANT_VMEM_BYTES = 12 * 1024 * 1024


def row_quant_block(B: int, K: int) -> int:
    """Rows per row-quantize block: 256, or the largest power of two that
    fits ``ROW_QUANT_VMEM_BYTES`` at width ``K`` (11264 -> 128; at 160 rows
    the compiler asked more than at 256), at least 32, or all of B when
    fewer."""
    if B < 256:
        return B
    bb = 256
    while bb > 32 and 6 * K * bb > ROW_QUANT_VMEM_BYTES:
        bb //= 2
    return bb


@functools.partial(jax.jit, static_argnames=("backend",))
def row_quantize(x: jax.Array, backend: Backend = "xla"):
    """x (B, K) -> (q int8 (B, K), state f32 (B, 1))."""
    if backend == "xla":
        return _ref.row_quantize(x)
    interp = backend == "pallas_interpret"
    B = x.shape[0]
    bb = row_quant_block(B, x.shape[1])
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


# ---------------------------------------------------------------------------
# grouped (mixture-of-experts) SwitchBack matmuls, kernels/switchback/grouped
# ---------------------------------------------------------------------------

# Rows per tile of the grouped kernels: each expert's slots are padded to a
# multiple of this, so a tile belongs to one expert.
MOE_TILE_ROWS = 256


def _divisors128(dim: int) -> list:
    """Multiples of 128 that divide ``dim``, or ``[dim]`` when none does."""
    return [b for b in range(128, dim + 1, 128) if dim % b == 0] or [dim]


# Widest column block of the grouped forward and input gradient. Each
# column block quantizes its row tile again and walks every tile of the
# grid, dead ones included, so one block over the whole width is fastest:
# at Moonlight's widths (2048 x 1408 up, 1408 x 2048 down) a call took
# 0.65-0.70 ms at 8 live tiles of 392 with whole-width blocks against
# 0.75-0.77 with 1024-wide ones, and no slower per live tile (a v5e).
MOE_COL_CAP = 2048


def moe_cols(dim: int, cap: int = MOE_COL_CAP) -> int:
    """Columns per block of a grouped kernel: the largest divisor of
    ``dim`` that is a multiple of 128 and at most ``cap``, else all of
    ``dim`` (1408 -> 1408, 2048 -> 2048, 7168 -> 1792)."""
    return max([b for b in _divisors128(dim) if b <= cap], default=dim)


def moe_wgrad_blocks(K: int, N: int, max_elems: int = 1 << 22
                     ) -> tuple[int, int]:
    """(block_k, block_n) of the grouped weight gradient: the largest f32
    output block (at most ``max_elems``, 16 MiB, double-buffered inside
    the kernels' 64 MiB of VMEM) of 128-multiple divisors. Each row tile
    is fetched once per output block and the grid walks every tile per
    block, so fewer, larger blocks read less and step less: 2048 x 1408
    whole took 0.76 ms at 8 live tiles against 0.98 in 512 x 1408 blocks
    (a v5e)."""
    pairs = [(bk, bn) for bk in _divisors128(K) for bn in _divisors128(N)
             if bk * bn <= max_elems]
    if not pairs:
        return min(_divisors128(K)), min(_divisors128(N))
    return max(pairs, key=lambda p: (p[0] * p[1], p[0]))


@functools.partial(jax.jit, static_argnames=("block_m", "out_dtype",
                                             "backend"))
def moe_gmm_fwd(x, w_q, s_w, tile_expert, n_live, *, block_m: int,
                out_dtype=jnp.bfloat16, backend: Backend = "xla"):
    """Grouped forward: rows of tile i (``block_m`` rows) times expert
    ``tile_expert[i]``'s int8 weight (w_q (E, K, N), s_w (E, 1, 1)), rows
    quantized per row. Rows of tiles from ``n_live`` (shape (1,)) on are
    left unwritten by the kernel (zeros on ``xla``)."""
    if backend == "xla":
        return _ref.moe_gmm_fwd(x, w_q, s_w, tile_expert, n_live,
                                block_m=block_m, out_dtype=out_dtype)
    return _g.gmm_fwd(x, w_q, s_w, tile_expert, n_live, block_m=block_m,
                      block_n=moe_cols(w_q.shape[2]),
                      out_dtype=out_dtype,
                      interpret=backend == "pallas_interpret")


@functools.partial(jax.jit, static_argnames=("block_m", "out_dtype",
                                             "backend"))
def moe_gmm_dgrad(g, w_q, s_w, tile_expert, n_live, *, block_m: int,
                  out_dtype=jnp.bfloat16, backend: Backend = "xla"):
    """Grouped input gradient: g (R, N) quantized per row times each tile's
    expert weight transposed (contracting w_q's N, never transposed)."""
    if backend == "xla":
        return _ref.moe_gmm_dgrad(g, w_q, s_w, tile_expert, n_live,
                                  block_m=block_m, out_dtype=out_dtype)
    return _g.gmm_dgrad(g, w_q, s_w, tile_expert, n_live, block_m=block_m,
                        block_k=moe_cols(w_q.shape[1]),
                        out_dtype=out_dtype,
                        interpret=backend == "pallas_interpret")


@functools.partial(jax.jit, static_argnames=("block_m", "n_experts",
                                             "backend"))
def moe_gmm_wgrad(x, g, tile_expert, n_live, *, block_m: int, n_experts: int,
                  backend: Backend = "xla"):
    """Grouped weight gradient (E, K, N) f32 from bf16 x (R, K) and g
    (R, N), summed over each expert's rows."""
    if backend == "xla":
        return _ref.moe_gmm_wgrad(x, g, tile_expert, n_live, block_m=block_m,
                                  n_experts=n_experts)
    bk, bn = moe_wgrad_blocks(x.shape[1], g.shape[1])
    return _g.gmm_wgrad(x, g, tile_expert, n_live, n_experts=n_experts,
                        block_m=block_m, block_k=bk, block_n=bn,
                        interpret=backend == "pallas_interpret")
