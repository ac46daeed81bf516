"""RWKV-6 "Finch" time-mix block (arXiv:2404.05892) — attention-free,
data-dependent per-channel decay.

Per head h with key/value dim hd, state S ∈ R^{hd×hd}:

    out_t = r_t · (diag(u)·(k_tᵀ v_t) + S_t)
    S_{t+1} = diag(w_t) S_t + k_tᵀ v_t

where w_t = exp(-exp(w0 + LoRA_w(x̃_t))) is the *data-dependent decay*
(the Finch innovation over RWKV-5's static decay) and x̃ are token-shifted
mixes: x̃ = lerp(x_t, x_{t-1}, μ + LoRA_μ(...)) per r/k/v/w/g channel.

Implementation detail (TPU adaptation, DESIGN.md §5): the recurrence is a
lax.scan over time in f32 — it is elementwise (no GEMM), so SwitchBack does
not apply to it; the surrounding r/k/v/g/output projections DO route
through quant_linear. A chunked (matmul-form) path for training speed is
provided in `rwkv6_chunked` and cross-checked against the scan in tests.

Simplifications vs the reference CUDA implementation (documented):
  * the 5 token-shift mixes use one shared LoRA per target (same shapes);
  * decay LoRA rank = cfg.rwkv.decay_lora (64 in the 1.6B config).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.precision import QuantPolicy, quant_linear
from repro.models.common import group_norm_heads

Array = jax.Array


class RWKVState(NamedTuple):
    wkv: Array        # (B, H, hd, hd) recurrent state
    x_prev: Array     # (B, D) previous time-mix input (for token shift)
    cm_x_prev: Array  # (B, D) previous channel-mix input (for token shift)


def _token_shift(x: Array, x_prev: Array) -> Array:
    """Shift sequence right by one; first position takes x_prev."""
    return jnp.concatenate([x_prev[:, None, :], x[:, :-1, :]], axis=1)


def _mix(x, x_shift, mu, lora_a, lora_b):
    """x̃ = x + (x_shift - x)·(μ + tanh((x_shift-x)·A)·B)  — Finch DDLerp."""
    dx = x_shift - x
    dyn = jnp.tanh(dx.astype(jnp.float32) @ lora_a.astype(jnp.float32))
    dyn = (dyn @ lora_b.astype(jnp.float32)).astype(x.dtype)
    return x + dx * (mu.astype(x.dtype) + dyn)


def _decay(xw: Array, p: dict) -> Array:
    """w_t = exp(-exp(w0 + tanh(x̃_w A_w) B_w)) ∈ (0, 1), per channel."""
    low = jnp.tanh(xw.astype(jnp.float32) @ p["w_lora_a"].astype(jnp.float32))
    low = low @ p["w_lora_b"].astype(jnp.float32)
    logw = p["w0"].astype(jnp.float32) + low
    return jnp.exp(-jnp.exp(logw))


def rwkv6_scan(r, k, v, w, u):
    """Sequential recurrence. r,k,v,w: (B, S, H, hd); u: (H, hd).
    Returns (out (B,S,H,hd) f32, final state (B,H,hd,hd))."""
    B, S, H, hd = r.shape
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp                       # (B, H, hd) each
        kv = kt[..., :, None] * vt[..., None, :]   # (B, H, hd, hd)
        out = jnp.einsum("bhk,bhkv->bhv", rt, uf[None, :, :, None] * kv + state)
        state = wt[..., :, None] * state + kv
        return state, out

    init = jnp.zeros((B, H, hd, hd), jnp.float32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf))
    final, outs = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(outs, 0, 1), final         # (B, S, H, hd)


def rwkv6_chunked(r, k, v, w, u, chunk: int = 64):
    """Chunk-parallel form: O(S/c) sequential steps of matmuls instead of
    O(S) elementwise steps — the MXU-friendly path (cf. Flash-Linear-
    Attention chunked algorithms). Exactly equals rwkv6_scan up to fp error.

    Within a chunk of length c (positions i, j ∈ [0, c)):
      intra: out_i += Σ_{j<i} (r_i ⊙ ∏_{m≤i-1,m>j} w_m? ) ... implemented
             via cumulative log-decay D = cumsum(log w) inside the chunk:
             A[i,j] = exp(D_i - D_{j+1})·(r_i·k_j) for j<i;  diag uses u.
      inter: out_i += (r_i ⊙ exp(D_i - D_0...)) S_chunk_start
    """
    B, S, H, hd = r.shape
    assert S % chunk == 0, "pad sequence to a chunk multiple"
    n = S // chunk
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))
    rc = rf.reshape(B, n, chunk, H, hd)
    kc = kf.reshape(B, n, chunk, H, hd)
    vc = vf.reshape(B, n, chunk, H, hd)
    lw = logw.reshape(B, n, chunk, H, hd)
    D = jnp.cumsum(lw, axis=2)                     # inclusive cumsum of log w
    uf = u.astype(jnp.float32)

    # intra-chunk pair term: A[b,n,h,i,j] = sum_d r_i k_j exp(D_{i-1}-D_j) for j<i
    # define E_i = D_{i-1} (exclusive cumsum)
    E = D - lw                                     # exclusive cumsum
    q_ = rc * jnp.exp(E)                           # r_i·exp(D_{i-1})
    k_ = kc * jnp.exp(-D)                          # k_j·exp(-D_j)
    A = jnp.einsum("bnihd,bnjhd->bnhij", q_, k_)
    idx = jnp.arange(chunk)
    A = jnp.where((idx[:, None] > idx[None, :])[None, None, None], A, 0.0)
    # diagonal (current token) bonus term: (B, n, chunk, H)
    diag = jnp.einsum("bnihd,bnihd->bnih", rc * uf[None, None, None], kc)
    out = jnp.einsum("bnhij,bnjhd->bnihd", A, vc)
    out = out + diag[..., None] * vc

    # inter-chunk: sequential scan over n chunks carrying S
    kv_chunk = jnp.einsum("bnjhd,bnjhe->bnhde",
                          kc * jnp.exp(D[:, :, -1:, :, :] - D), vc)
    decay_chunk = jnp.exp(D[:, :, -1])             # (B, n, H, hd) total decay

    def step(S0, inp):
        q_i, dec, kv = inp
        out_inter = jnp.einsum("bihd,bhde->bihe", q_i, S0)
        S1 = dec[..., None] * S0 + kv
        return S1, out_inter

    init = jnp.zeros((B, H, hd, hd), jnp.float32)
    xs = (jnp.moveaxis(rc * jnp.exp(E), 1, 0),
          jnp.moveaxis(decay_chunk, 1, 0),
          jnp.moveaxis(kv_chunk, 1, 0))
    final, inter = jax.lax.scan(step, init, xs)
    out = out + jnp.moveaxis(inter, 0, 1)
    return out.reshape(B, S, H, hd), final


def rwkv6_block(x: Array, p: dict, cfg, policy: QuantPolicy, *,
                state: RWKVState | None = None, use_chunked: bool = True):
    """Full RWKV-6 time-mix sub-block. x: (B, S, D).
    Returns (out (B,S,D), new_state)."""
    B, S, D = x.shape
    H = D // cfg.rwkv.head_dim
    hd = cfg.rwkv.head_dim
    x_prev = state.x_prev if state is not None else jnp.zeros((B, D), x.dtype)
    xs = _token_shift(x, x_prev)

    xr = _mix(x, xs, p["mu_r"], p["mix_lora_a"], p["mix_lora_b_r"])
    xk = _mix(x, xs, p["mu_k"], p["mix_lora_a"], p["mix_lora_b_k"])
    xv = _mix(x, xs, p["mu_v"], p["mix_lora_a"], p["mix_lora_b_v"])
    xw = _mix(x, xs, p["mu_w"], p["mix_lora_a"], p["mix_lora_b_w"])
    xg = _mix(x, xs, p["mu_g"], p["mix_lora_a"], p["mix_lora_b_g"])

    cd = policy.compute_dtype
    r = quant_linear(xr, p["wr"].astype(cd), policy=policy).reshape(B, S, H, hd)
    k = quant_linear(xk, p["wk"].astype(cd), policy=policy).reshape(B, S, H, hd)
    v = quant_linear(xv, p["wv"].astype(cd), policy=policy).reshape(B, S, H, hd)
    g = quant_linear(xg, p["wg"].astype(cd), policy=policy)
    w = _decay(xw, p).reshape(B, S, H, hd)
    u = p["u"].reshape(H, hd)

    s0 = state.wkv if state is not None else jnp.zeros((B, H, hd, hd),
                                                       jnp.float32)
    if S == 1:
        # decode step: single recurrence update, no scan
        kv = k[:, 0, :, :, None].astype(jnp.float32) * \
             v[:, 0, :, None, :].astype(jnp.float32)
        out = jnp.einsum("bhk,bhkv->bhv", r[:, 0].astype(jnp.float32),
                         u.astype(jnp.float32)[None, :, :, None] * kv + s0)
        new_s = w[:, 0].astype(jnp.float32)[..., None] * s0 + kv
        out = out[:, None]
    elif use_chunked and S % 64 == 0 and state is None:
        out, new_s = rwkv6_chunked(r, k, v, w, u)
    else:
        out, new_s = rwkv6_scan(r, k, v, w, u)
        if state is not None:
            # fold initial state contribution (scan started from zeros)
            decay_prod = jnp.exp(jnp.cumsum(
                jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38)), axis=1))
            pre = jnp.einsum("bshk,bhkv->bshv", r.astype(jnp.float32) *
                             jnp.roll(decay_prod, 1, axis=1).at[:, 0].set(1.0),
                             s0)
            out = out + pre
            new_s = new_s + decay_prod[:, -1][..., None] * s0

    out = out.reshape(B, S, D).astype(x.dtype)
    out = group_norm_heads(out, p["ln_x"], H)
    out = out * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    out = quant_linear(out, p["wo"].astype(policy.compute_dtype),
                       policy=policy)
    cm_prev = (state.cm_x_prev if state is not None
               else jnp.zeros((B, D), x.dtype))
    new_state = RWKVState(new_s, x[:, -1, :], cm_prev)
    return out, new_state


def rwkv_channel_mix(x: Array, p: dict, cfg, policy: QuantPolicy, *,
                     x_prev: Array | None = None):
    """RWKV channel-mix (the FFN analogue): squared-ReLU K, sigmoid R gate."""
    B, S, D = x.shape
    xp = x_prev if x_prev is not None else jnp.zeros((B, D), x.dtype)
    xs = _token_shift(x, xp)
    xk = _mix(x, xs, p["mu_ck"], p["mix_lora_a"], p["mix_lora_b_ck"])
    xr = _mix(x, xs, p["mu_cr"], p["mix_lora_a"], p["mix_lora_b_cr"])
    cd = policy.compute_dtype
    kk = quant_linear(xk, p["w_key"].astype(cd), policy=policy)
    kk = jnp.square(jax.nn.relu(kk.astype(jnp.float32))).astype(x.dtype)
    vv = quant_linear(kk, p["w_value"].astype(cd), policy=policy)
    rr = jax.nn.sigmoid(quant_linear(
        xr, p["w_receptance"].astype(cd), policy=policy).astype(jnp.float32))
    return (rr.astype(x.dtype) * vv), x[:, -1, :]
