"""Token-choice top-k mixture of experts, dropless over the experts that
this chip holds.

Under expert parallelism every chip routes its tokens over all
``moe.n_experts`` experts at the router's published width and computes
the part of the result that its own experts give: ``cfg.held`` names them
(first id, count; all of them when nothing is held elsewhere). On one
chip the layer runs without the exchange; what absent experts would add
is left out, and that partial result goes on to the next layer.

* ``moe.router``   f32 scores over all experts: softmax (top-k on the
  probabilities) or sigmoid with a selection-only bias (DeepSeek-V3
  ``noaux_tc``: top-k on score + bias, gates are the chosen raw scores);
  gates renormalised to 1 and scaled by ``routed_scale``; the balance loss
  (Switch top-1 density, or V3's sequence-wise one) times ``balance_alpha``.
* ``moe.dispatch`` a counting sort of the held slots by expert: each
  expert's slots fill whole tiles of ``TILE_ROWS`` rows (an expert with no
  slot gets one empty tile), so a tile belongs to one expert. No slot is
  dropped: the slot buffer's static bound is every slot that can be held,
  ``T * min(top_k, held)`` (a token's k experts are distinct), plus one
  tile per held expert of padding.
* ``moe.experts``  SwiGLU through the grouped SwitchBack kernels
  (``core/switchback.make_grouped_switchback``; ``jax.lax.ragged_dot`` in
  16/32-bit modes), which compute only the live tiles.
* ``moe.shared``   the shared experts, one SwiGLU of ``n_shared`` experts'
  width through ``quant_linear``.
* ``moe.combine``  each token's held slots gathered back and summed with
  their gates.

Per step the layer also returns routing counters (held slots, max/mean
load over the held experts and over all of them), which ride the step's
metrics to the host at the trainer's flush.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import switchback as SB
from repro.core.precision import QuantPolicy, variant_for_mode
from repro.kernels.switchback.ops import MOE_TILE_ROWS
from repro.models import params as PRM
from repro.models.common import activation
from repro.models.mlp import mlp_block

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST

TILE_ROWS = MOE_TILE_ROWS


def route(x: Array, p: dict, moe, batch: int):
    """x (T, D) of ``batch`` sequences. Returns (gates (T, k) f32, experts
    (T, k) int32, balance loss already times ``balance_alpha``, counts
    (n_experts,) of slots per expert)."""
    E, K = moe.n_experts, moe.top_k
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["w_router"].astype(jnp.float32), precision=HIGHEST)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(p["bias"]), K)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(scores, K)
    if moe.norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-20)
    gates = gates * moe.routed_scale
    counts = jnp.zeros((E,), jnp.int32).at[experts.reshape(-1)].add(1)
    if moe.balance == "seq":
        # DeepSeek-V3: per sequence, f_i = E/(k S) x slots to expert i and
        # P_i = mean over its tokens of the scores normalised over experts
        S = x.shape[0] // batch
        hits = jax.nn.one_hot(experts.reshape(batch, S * K), E,
                              dtype=jnp.float32).sum(1) * (E / (K * S))
        norm = scores / jnp.sum(scores, -1, keepdims=True)
        aux = jnp.mean(jnp.sum(hits * norm.reshape(batch, S, E).mean(1), -1))
    else:
        density = jnp.mean(jax.nn.one_hot(experts[:, 0], E), axis=0)
        aux = jnp.sum(density * jnp.mean(scores, axis=0)) * E
    return gates, experts.astype(jnp.int32), moe.balance_alpha * aux, counts


def slot_bound(T: int, top_k: int, n_held: int, tile: int = TILE_ROWS) -> int:
    """Static rows of the slot buffer: all ``T * min(top_k, n_held)`` slots
    that can go to held experts, rounded up to a tile, plus one tile per
    held expert (its padding to whole tiles, or its one empty tile)."""
    return -(-T * min(top_k, n_held) // tile) * tile + n_held * tile


def plan(experts: Array, first: int, n_held: int, tile: int = TILE_ROWS):
    """Counting sort of the held slots by expert. experts (T, k). Returns
    (dest (T*k,) row of each slot, R for one not held; row_slot (R,) slot
    of each row, -1 for padding; tile_expert (R/tile,); n_live (1,);
    sizes (n_held,) slots per held expert)."""
    T, K = experts.shape
    R = slot_bound(T, K, n_held, tile)
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    onehot = jax.nn.one_hot(key, n_held + 1, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    sizes = jnp.sum(onehot, axis=0)[:n_held]
    tiles = jnp.maximum(1, -(-sizes // tile))
    tile_end = jnp.cumsum(tiles)
    row0 = jnp.concatenate([tile_end - tiles, jnp.zeros((1,), jnp.int32)])
    dest = jnp.where(held, row0[key] * tile + rank, R)
    row_slot = jnp.full((R,), -1, jnp.int32).at[dest].set(
        jnp.arange(T * K, dtype=jnp.int32), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(R // tile), side="right"),
        n_held - 1).astype(jnp.int32)
    return dest, row_slot, tile_expert, tile_end[-1:], sizes


def _take(src: Array, idx: Array) -> Array:
    ok = (idx >= 0) & (idx < src.shape[0])
    rows = src[jnp.clip(idx, 0, src.shape[0] - 1)]
    return jnp.where(ok[:, None], rows, jnp.zeros((), src.dtype))


@jax.custom_vjp
def take_rows(src: Array, idx: Array, inv: Array) -> Array:
    """Rows ``src[idx]``, zero where ``idx`` is out of range. ``inv`` (N, J)
    names, for each of src's N rows, the output rows that read it (out of
    range where fewer than J do). The gradient then gathers too: src's
    gradient row sums its J readers' rows, and no scatter-add runs."""
    return _take(src, idx)


def _take_rows_fwd(src, idx, inv):
    return _take(src, idx), inv


def _take_rows_bwd(inv, g):
    N, J = inv.shape
    parts = _take(g, inv.reshape(-1)).reshape(N, J, g.shape[-1])
    dsrc = jnp.sum(parts.astype(jnp.float32), axis=1).astype(g.dtype)
    return dsrc, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def grouped_linear(x: Array, w: Array, tile_expert: Array, n_live: Array,
                   policy: QuantPolicy) -> Array:
    """Rows of each tile times its expert's weight (w (E, n, m))."""
    cd = policy.compute_dtype
    if policy.is_quantized and variant_for_mode(policy.mode) == "switchback":
        f = SB.make_grouped_switchback(policy.backend, TILE_ROWS)
        return f(x.astype(cd), w.astype(jnp.float32), tile_expert, n_live)
    tile = jnp.arange(x.shape[0]) // TILE_ROWS
    if policy.is_quantized:
        # other quantized variants (simulation and tests): every expert's
        # dense SwitchBack over every row, each row taking its own expert's
        f = SB.make_switchback_matmul(variant_for_mode(policy.mode),
                                      policy.fwd_fmt, policy.bwd_fmt,
                                      policy.backend)
        xs = jnp.broadcast_to(x.astype(cd), (w.shape[0],) + x.shape)
        ys = jax.vmap(f)(xs, w.astype(jnp.float32))
        return jnp.take_along_axis(
            ys, tile_expert[tile][None, :, None], axis=0)[0]
    live = tile < n_live[0]
    sizes = jnp.zeros((w.shape[0] + 1,), jnp.int32).at[
        jnp.where(live[::TILE_ROWS], tile_expert, w.shape[0])].add(
        TILE_ROWS)[:-1]
    return jax.lax.ragged_dot(x.astype(cd), w.astype(cd), sizes,
                              preferred_element_type=jnp.float32).astype(cd)


def moe_block(x: Array, p: dict, cfg, policy: QuantPolicy):
    """x (B, S, D) -> (out (B, S, D), balance loss, counters dict)."""
    moe = cfg.moe
    B, S, D = x.shape
    T = B * S
    first, n_held = cfg.held
    cd = policy.compute_dtype
    x2 = x.reshape(T, D)
    with jax.named_scope("moe.router"):
        gates, experts, aux, counts = route(x2, p, moe, B)
    with jax.named_scope("moe.dispatch"):
        dest, row_slot, tile_expert, n_live, sizes = plan(experts, first,
                                                          n_held)
        row_token = jnp.where(row_slot >= 0, row_slot // moe.top_k, -1)
        rows = take_rows(x2.astype(cd), row_token, dest.reshape(T, -1))
    with jax.named_scope("moe.experts"):
        h = grouped_linear(rows, p["w_up"], tile_expert, n_live, policy)
        g = (grouped_linear(rows, p["w_gate"], tile_expert, n_live, policy)
             if "w_gate" in p else None)
        y = grouped_linear(activation(h, g, cfg.act), p["w_down"],
                           tile_expert, n_live, policy)
    with jax.named_scope("moe.combine"):
        ys = take_rows(y, dest, row_slot[:, None]).reshape(T, -1, D)
        out = jnp.einsum("tk,tkd->td", gates.astype(cd), ys,
                         preferred_element_type=jnp.float32).astype(cd)
    if moe.n_shared:
        with jax.named_scope("moe.shared"):
            out = out + mlp_block(x2.astype(cd), p["shared"], cfg,
                                  policy).astype(cd)
    out = PRM.constrain(out.reshape(B, S, D).astype(x.dtype),
                        ("batch", "seq", "embed"))
    load = lambda c: (jnp.max(c) / jnp.maximum(jnp.mean(c), 1e-9)
                      ).astype(jnp.float32)
    stats = {"moe/held_slots": jnp.sum(sizes).astype(jnp.float32),
             "moe/load_max_over_mean.held": load(sizes.astype(jnp.float32)),
             "moe/load_max_over_mean.all": load(counts.astype(jnp.float32)),
             "moe/balance_loss": aux}
    return out, aux, stats
