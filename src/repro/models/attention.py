"""Grouped-query attention with RoPE, flash-style chunked softmax, KV cache.

Three implementations, selected by ``QuantPolicy.backend`` + the
``attn_impl`` knob:

* ``dense``      — materializes (B, H, Sq, Sk) scores; fine for short
                   seqs and the numerics oracle every other path is
                   tested against.
* ``flash_scan`` — online-softmax over KV chunks via lax.scan; the score
                   matrix never exceeds (B, H, Sq, chunk). The pure-XLA
                   fallback for the 32k prefill shapes.
* ``pallas``     — the fused flash-attention kernels in
                   kernels/flash_attention (fwd + custom-VJP bwd + decode
                   ring-cache kernel), dispatched whenever the policy's
                   kernel backend is ``pallas``/``pallas_interpret`` —
                   the same one-knob discipline as the SwitchBack int8
                   matmuls (DESIGN.md §9). GQA runs natively (no
                   ``jnp.repeat`` head expansion on the kernel path).

All projections route through ``quant_linear`` so SwitchBack (the paper's
technique) applies to K/Q/V/out exactly as described in paper §1.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import QuantPolicy, quant_linear
from repro.core.sharding import kernel_call
from repro.kernels.flash_attention import ops as FA
from repro.kernels.paged_attention import ops as PA
from repro.kernels.paged_attention.ref import gather_blocks
from repro.models import params as PRM
from repro.models.common import apply_rope, apply_rope_cached, rms_norm

Array = jax.Array
NEG_INF = -2.0e38

# policy backends routed to the fused Pallas kernels; "xla" keeps the
# dense / flash_scan reference paths
FLASH_BACKENDS = ("pallas", "pallas_interpret")


class KVCache(NamedTuple):
    """Preallocated KV cache for autoregressive decode.

    ``length`` comes in two shapes selecting two write/mask disciplines:

    * scalar int32 — the classic single-sequence cache: every batch row is
      at the same position (``decode_step`` in models/transformer.py).
    * ``(B,)`` int32 — the *serve* cache: each batch slot tracks its own
      absolute token count, writes land at ``length % S_max`` (ring buffer,
      so sequences longer than the cache keep the last ``S_max`` tokens)
      and attention masks each slot to its own valid prefix. This is what
      continuous batching needs: slots admit/evict independently.

    The head axis sits ahead of the token axis so the decode kernel reads
    one head's tile as a whole trailing (tokens, hd) slab.
    """
    k: Array          # (B, n_kv, S_max, hd)
    v: Array          # (B, n_kv, S_max, hd)
    length: Array     # int32 — scalar, or (B,) per-slot (see above)


def qkv_project(x: Array, p: dict, cfg, policy: QuantPolicy):
    """x: (B, S, D) -> q (B,S,H,hd), k,v (B,S,KV,hd)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    cd = policy.compute_dtype
    wq = p["wq"].astype(cd)
    wk = p["wk"].astype(cd)
    wv = p["wv"].astype(cd)
    q = quant_linear(x, wq, policy=policy).reshape(B, S, H, hd)
    k = quant_linear(x, wk, policy=policy).reshape(B, S, KV, hd)
    v = quant_linear(x, wv, policy=policy).reshape(B, S, KV, hd)
    return q, k, v


def _expand_kv(k: Array, n_heads: int) -> Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each KV head."""
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    return jnp.repeat(k, rep, axis=2) if rep > 1 else k


def dense_attention(q: Array, k: Array, v: Array, *, causal: bool,
                    q_offset: int | Array = 0,
                    kv_len: Optional[Array] = None) -> Array:
    """Standard softmax attention. q: (B,Sq,H,hd); k,v: (B,Sk,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal or kv_len is not None:
        kpos = jnp.arange(Sk)[None, None, None, :]
        mask = jnp.zeros((1, 1, 1, Sk), jnp.bool_)
        if causal:
            qpos = q_offset + jnp.arange(Sq)
            mask = mask | (kpos > qpos[None, None, :, None])
        if kv_len is not None:
            mask = mask | (kpos >= kv_len)
        s = jnp.where(mask, NEG_INF, s)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", a, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_scan_attention(q: Array, k: Array, v: Array, *, causal: bool,
                         chunk: int = 1024) -> Array:
    """Online-softmax attention, scanning over KV chunks.

    Memory: O(B·H·Sq·chunk) scores instead of O(B·H·Sq·Sk). The scan keeps
    running (max, denominator, weighted-sum) per query — numerically
    identical to softmax attention up to fp error.

    Chunks that are fully masked for *every* query are not scanned at all
    (a static bound): trailing KV padding, and — for causal ``Sq == Sk`` —
    anything past the last query's position. Queries whose whole window is
    skipped (only possible for pad queries) come out zero.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if Sk % chunk:
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pad_mask_len = Sk
        Sk = k.shape[1]
    else:
        pad_mask_len = None
    # static live-chunk bound: keys >= pad_mask_len are pad; with causal
    # masking keys >= Sq are invisible to every query — either way the
    # trailing chunks contribute exp(-inf) ≡ 0 and are skipped, so the
    # XLA fallback stops paying matmuls for padding
    limit = Sk if pad_mask_len is None else pad_mask_len
    if causal:
        limit = min(limit, Sq)
    n_chunks = max(1, -(-limit // chunk))
    k = k[:, :n_chunks * chunk]
    v = v[:, :n_chunks * chunk]
    Sk = k.shape[1]
    dv = v.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = q.astype(jnp.float32) * scale
    kc = k.reshape(B, n_chunks, chunk, H, hd)
    vc = v.reshape(B, n_chunks, chunk, H, dv)
    qpos = jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry                     # (B,H,Sq), (B,H,Sq), (B,H,Sq,hd)
        kb, vb, c_idx = inp                   # (B,chunk,H,hd) ×2, scalar
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        kpos = c_idx * chunk + jnp.arange(chunk)
        mask = jnp.zeros((Sq, chunk), jnp.bool_)
        if causal:
            mask = mask | (kpos[None, :] > qpos[:, None])
        if pad_mask_len is not None:
            mask = mask | (kpos[None, :] >= pad_mask_len)
        s = jnp.where(mask[None, None], NEG_INF, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, H, Sq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, Sq), jnp.float32),
            jnp.zeros((B, H, Sq, dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, init,
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)   # (B,Sq,H,hd)


def _core_attention(q: Array, k: Array, v: Array, *, causal: bool,
                    policy: QuantPolicy, impl: str = "flash_scan",
                    block_q: int = 0, block_k: int = 0) -> Array:
    """Backend-dispatched attention core. q (B, Sq, H, dqk); k (B, Sk, KV,
    dqk) and v (B, Sk, KV, dv) with KV heads *folded* — the Pallas kernels consume
    GQA natively (BlockSpec maps query head h to KV head h // group); the
    XLA paths expand heads with ``jnp.repeat`` as before. ``impl="dense"``
    forces the oracle regardless of backend. Every op here, the layout
    moves and padding around the kernels included, sits in the
    ``attention`` scope."""
    with jax.named_scope("attention"):
        if impl != "dense" and policy.backend in FLASH_BACKENDS:
            return kernel_call(
                functools.partial(FA.flash_attention, causal=causal,
                                  backend=policy.backend, block_q=block_q,
                                  block_k=block_k),
                q, k, v, batch_dims=(0, 0, 0), backend=policy.backend)
        n_heads = q.shape[2]
        kx = _expand_kv(k, n_heads)
        vx = _expand_kv(v, n_heads)
        if impl == "flash_scan" and q.shape[1] > 2048:
            return flash_scan_attention(q, kx, vx, causal=causal)
        return dense_attention(q, kx, vx, causal=causal)


def attention_block(x: Array, p: dict, cfg, policy: QuantPolicy, *,
                    positions: Array, causal: bool = True,
                    impl: str = "flash_scan", block_q: int = 0,
                    block_k: int = 0) -> Array:
    """Full self-attention sub-block: QKV proj -> RoPE -> attn -> out proj."""
    q, k, v = qkv_project(x, p, cfg, policy)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = PRM.constrain(q, ("batch", None, "heads", None))
    k = PRM.constrain(k, ("batch", None, "kv_heads", None))
    o = _core_attention(q, k, v, causal=causal, policy=policy, impl=impl,
                        block_q=block_q, block_k=block_k)
    o = o.reshape(x.shape[0], x.shape[1], cfg.n_heads * cfg.hd)
    wo = p["wo"].astype(policy.compute_dtype)
    return quant_linear(o, wo, policy=policy)


def mla_block(x: Array, p: dict, cfg, policy: QuantPolicy, *,
              positions: Array, impl: str = "flash_scan", block_q: int = 0,
              block_k: int = 0) -> Array:
    """Multi-head latent attention for training (DeepSeek-V2/V3 without a
    query LoRA): q straight from x; x down to a ``kv_lora_rank`` latent,
    RMS-normed and up-projected to per-head ``qk_nope_dim`` keys and
    ``v_dim`` values, plus one ``qk_rope_dim`` key part that every head
    shares. RoPE (split halves) goes on the query's and that shared key's
    rope parts; queries and keys are ``qk_nope_dim + qk_rope_dim`` wide and
    values ``v_dim`` wide, so the core runs at ``dv != dqk`` with the
    softmax scale 1/sqrt(dqk). Every projection is a ``quant_linear``."""
    m = cfg.mla
    B, S, _ = x.shape
    H, dn, dr, dv, r = (cfg.n_heads, m.qk_nope_dim, m.qk_rope_dim, m.v_dim,
                        m.kv_lora_rank)
    cd = policy.compute_dtype
    with jax.named_scope("mla"):
        q = quant_linear(x, p["wq"].astype(cd),
                         policy=policy).reshape(B, S, H, dn + dr)
        kv_a = quant_linear(x, p["wkv_a"].astype(cd), policy=policy)
        c = rms_norm(kv_a[..., :r], p["kv_norm"]["scale"], cfg.norm_eps)
        k_rope = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta)
        kv = quant_linear(c, p["wkv_b"].astype(cd),
                          policy=policy).reshape(B, S, H, dn + dv)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        q = PRM.constrain(q, ("batch", None, "heads", None))
        k = PRM.constrain(k, ("batch", None, "heads", None))
        o = _core_attention(q, k, kv[..., dn:], causal=True, policy=policy,
                            impl=impl, block_q=block_q, block_k=block_k)
        wo = p["wo"].astype(cd)
        return quant_linear(o.reshape(B, S, H * dv), wo, policy=policy)


# ---------------------------------------------------------------------------
# decode path (KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (batch, cfg.n_kv_heads, max_len, cfg.hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


def attention_decode_step(x: Array, cache: KVCache, p: dict, cfg,
                          policy: QuantPolicy, *, rope_cache=None,
                          impl: str = "flash_scan",
                          block_k: int = 0) -> tuple[Array, KVCache]:
    """One-token decode: x (B, 1, D); cache holds `length` past tokens.

    With a scalar cache length every row writes at the same offset; with a
    per-slot ``(B,)`` length each slot writes at its own ring position
    ``length[b] % S_max`` and attends over ``min(length[b]+1, S_max)``
    valid cells. RoPE is applied at write time with the token's absolute
    position, so a wrapped (sliding-window) cache needs no per-cell
    position bookkeeping — the rotation is already baked into stored keys.
    ``rope_cache=(cos, sin)`` rows pre-gathered for this step's positions
    (the serve engine hoists the tables; see models/common.rope_tables)
    replaces the in-layer cos/sin computation bit-identically.

    On the Pallas backends the re-attend runs the fused decode kernel:
    per-slot lengths ride into the kernel and tiles beyond a slot's valid
    prefix are skipped dynamically, instead of the dense full-``S_max``
    re-attend the XLA path pays. ``impl="dense"`` forces the oracle on
    every backend (the same escape hatch as ``attention_block``).
    """
    B = x.shape[0]
    per_slot = cache.length.ndim == 1
    S_max = cache.k.shape[2]
    if per_slot:
        pos = cache.length[:, None]                      # (B, 1) per-slot pos
    else:
        pos = jnp.broadcast_to(cache.length[None, None], (B, 1))
    q, k, v = qkv_project(x, p, cfg, policy)
    if rope_cache is not None:
        q = apply_rope_cached(q, *rope_cache)
        k = apply_rope_cached(k, *rope_cache)
    else:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if per_slot:
        write_at = cache.length % S_max                  # ring write position
        rows = jnp.arange(B)
        k_cache = cache.k.at[rows, :, write_at].set(
            k[:, 0].astype(cache.k.dtype))
        v_cache = cache.v.at[rows, :, write_at].set(
            v[:, 0].astype(cache.v.dtype))
        valid = jnp.minimum(cache.length + 1, S_max)     # (B,)
        kv_len = valid[:, None, None, None]
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache.k, jnp.swapaxes(k, 1, 2).astype(cache.k.dtype),
            cache.length, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache.v, jnp.swapaxes(v, 1, 2).astype(cache.v.dtype),
            cache.length, axis=2)
        valid = jnp.broadcast_to(cache.length + 1, (B,))
        kv_len = cache.length + 1
    if impl != "dense" and policy.backend in FLASH_BACKENDS:
        o = kernel_call(
            functools.partial(FA.decode_attention, backend=policy.backend,
                              block_k=block_k),
            q, k_cache, v_cache, valid, batch_dims=(0, 0, 0, 0),
            backend=policy.backend)
    else:
        kx = _expand_kv(jnp.swapaxes(k_cache, 1, 2), cfg.n_heads)
        vx = _expand_kv(jnp.swapaxes(v_cache, 1, 2), cfg.n_heads)
        o = dense_attention(q, kx, vx, causal=False, kv_len=kv_len)
    o = o.reshape(B, 1, cfg.n_heads * cfg.hd)
    wo = p["wo"].astype(policy.compute_dtype)
    out = quant_linear(o, wo, policy=policy)
    return out, KVCache(k_cache, v_cache, cache.length + 1)


def attention_prefill(x: Array, cache: KVCache, p: dict, cfg,
                      policy: QuantPolicy, *, admit: Array, rope_cache=None,
                      impl: str = "flash_scan", block_q: int = 0,
                      block_k: int = 0) -> tuple[Array, KVCache]:
    """Full-prompt attention that also seeds the serve cache.

    x: (B, S, D) prompts padded to S (S <= S_max); ``admit``: (B,) bool —
    slots being (re)filled. The attention math is exactly
    ``attention_block``'s dense path over positions [0, S), so prefill
    logits match the training/teacher-forcing forward bit-for-bit in f32;
    pad positions beyond a slot's true prompt length produce garbage that
    the per-slot length mask (set by the caller) hides from later steps.
    Non-admitted slots compute the same attention but their cache rows are
    left untouched — live sequences in other slots are unaffected.
    """
    B, S, _ = x.shape
    assert cache.length.ndim == 1, "prefill needs a per-slot (serve) cache"
    positions = jnp.arange(S)
    q, k, v = qkv_project(x, p, cfg, policy)
    if rope_cache is not None:
        q = apply_rope_cached(q, *rope_cache)
        k = apply_rope_cached(k, *rope_cache)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = PRM.constrain(q, ("batch", None, "heads", None))
    k = PRM.constrain(k, ("batch", None, "kv_heads", None))
    # the prefill attention must match attention_block's forward on the
    # same tokens (the serve parity invariant): both dispatch through the
    # same (impl, backend) rule — flash kernels on pallas*, dense (or
    # flash_scan past its threshold) on xla, oracle under impl="dense"
    o = _core_attention(q, k, v, causal=True, policy=policy, impl=impl,
                        block_q=block_q, block_k=block_k)
    o = o.reshape(B, S, cfg.n_heads * cfg.hd)
    wo = p["wo"].astype(policy.compute_dtype)
    out = quant_linear(o, wo, policy=policy)
    sel = admit[:, None, None, None]
    k_cache = jnp.where(sel, cache.k.at[:, :, :S].set(
        jnp.swapaxes(k, 1, 2).astype(cache.k.dtype)), cache.k)
    v_cache = jnp.where(sel, cache.v.at[:, :, :S].set(
        jnp.swapaxes(v, 1, 2).astype(cache.v.dtype)), cache.v)
    return out, KVCache(k_cache, v_cache, cache.length)


# ---------------------------------------------------------------------------
# paged serving (block-pool KV cache, DESIGN.md §10)
# ---------------------------------------------------------------------------

class PagedKVCache(NamedTuple):
    """Block-pool KV cache for paged serving.

    ``k``/``v`` are pools of physical blocks shared by every batch slot,
    shape (num_blocks + 1, n_kv, block_size, hd); the **last** block is
    the trash block that absorbs writes from idle slots and masked pad
    positions (racy writes there are by construction never read). Which
    logical cell of which slot lives in which physical block is decided
    host-side (serve/paged/block_pool.py) and rides into the jitted steps
    as a (B, n_blocks_per_slot) int32 **block table**: cell ``j*bs + o``
    of slot b is pool cell ``(tables[b, j], :, o)``. ``length`` is the
    per-slot absolute token count — same semantics as the per-slot ring
    cache, but cache memory scales with allocated blocks (live tokens),
    not max_batch × max_len.
    """
    k: Array          # (num_blocks + 1, n_kv, block_size, hd)
    v: Array
    length: Array     # (B,) int32 absolute tokens per slot


def _paged_commit(buf: Array, vals: Array, phys: Array, off: Array) -> Array:
    """Scatter token KVs into pool cells. buf (N+1, KV, bs, hd); vals
    (T, KV, hd); phys/off (T,) int32. Masked writes are routed to the
    trash block by the caller; duplicate targets only ever occur there."""
    return buf.at[phys, :, off].set(vals.astype(buf.dtype))


def attention_paged_prefill(x: Array, cache: PagedKVCache, tables: Array,
                            p: dict, cfg, policy: QuantPolicy, *,
                            admit: Array, pref_lens: Array,
                            prompt_lens: Array, rope_cache=None,
                            impl: str = "flash_scan"
                            ) -> tuple[Array, PagedKVCache]:
    """Chunked prefill over a block table: run the prompt *suffix* whose
    KV isn't yet resident (not adopted from the prefix cache, not
    committed by an earlier chunk), attending to the resident blocks plus
    the suffix's own causal keys.

    x: (B, S, D) suffix tokens (positions ``pref_lens[b] + [0, S)`` of
    each prompt) right-padded to a common S; ``pref_lens``: (B,) resident
    prefix lengths — adopted full blocks at admission, or the chunked-
    prefill progress cursor on resumed chunks; ``prompt_lens``: (B,)
    prefill targets (cursor + chunk for a mid-prompt chunk); ``admit``:
    (B,) bool. The suffix K/V are committed into the slot's table blocks
    at block granularity (non-admitted and pad positions land in the
    trash block), so live neighbours' blocks are untouched.

    With ``pref_lens == 0`` the math reduces exactly to the ring path's
    dense prefill — prefix columns are masked to NEG_INF and contribute
    exact zeros — which is what the paged-vs-ring parity tests pin.
    Prefix *and* suffix K/V attend in cache dtype (commit-then-attend:
    what the pool stores is what the scores see) so a later decode —
    or a speculative verify pass re-scoring the same positions — reads
    bit-identical keys. On ``xla`` (or ``impl="dense"``) the attention
    is the gather-then-concat dense oracle; on the Pallas backends the
    suffix KV is committed *first* and the per-slot-offset flash prefill
    kernel streams prefix and suffix uniformly from the pool. Both are
    value-identical to the ring dense prefill when cache and compute
    dtype agree — which is what the parity tests pin."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bs, nb = cache.k.shape[2], tables.shape[1]
    trash = cache.k.shape[0] - 1
    positions = pref_lens[:, None] + jnp.arange(S)[None, :]   # (B, S) abs
    q, k, v = qkv_project(x, p, cfg, policy)
    if rope_cache is not None:
        q = apply_rope_cached(q, *rope_cache)
        k = apply_rope_cached(k, *rope_cache)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = PRM.constrain(q, ("batch", None, "heads", None))
    k = PRM.constrain(k, ("batch", None, "kv_heads", None))

    # commit the suffix KV at block granularity; masked positions -> trash
    valid = admit[:, None] & (positions < prompt_lens[:, None])
    logical = jnp.clip(positions // bs, 0, nb - 1)
    phys = jnp.where(valid, jnp.take_along_axis(tables, logical, axis=1),
                     trash).reshape(-1)
    off = jnp.where(valid, positions % bs, 0).reshape(-1)
    k_buf = _paged_commit(cache.k, k.reshape(B * S, KV, hd), phys, off)
    v_buf = _paged_commit(cache.v, v.reshape(B * S, KV, hd), phys, off)

    backend = (policy.backend if impl != "dense"
               and policy.backend in FLASH_BACKENDS else "xla")
    if backend in FLASH_BACKENDS:
        # commit-then-attend: with the chunk's KV just landed, the fused
        # kernel reads prefix and suffix through the table in one sweep —
        # no (B, nb*bs, H, hd) gather+concat materialisation
        kv_valid = jnp.where(admit, prompt_lens, 0)
        o = kernel_call(
            functools.partial(PA.paged_prefill_attention, backend=backend),
            q, k_buf, v_buf, tables, pref_lens, kv_valid,
            batch_dims=(0, None, None, 0, 0, 0), backend=backend)
    else:
        # resident prefix, gathered through the table in logical order
        # (from the pre-commit pools — commit cells are masked dead below,
        # so the read set is disjoint from the cells written above). The
        # suffix K/V round-trip through the cache dtype so the oracle
        # attends the same bits the pool holds — commit-then-attend, like
        # the kernel path. Decode re-reads these cells rounded, so the
        # speculative verify pass (Sq = k+1 through this function) scores
        # draft positions with the same values a plain decode would; fresh
        # compute-dtype suffix keys would put ~bf16-epsilon noise on the
        # logits and flip greedy argmax at near-ties, breaking spec/off
        # token parity.
        k_suf = k.astype(cache.k.dtype).astype(k.dtype)
        v_suf = v.astype(cache.v.dtype).astype(v.dtype)
        k_pref = gather_blocks(cache.k, tables)       # (B, nb*bs, KV, hd)
        v_pref = gather_blocks(cache.v, tables)
        kx = jnp.concatenate([_expand_kv(k_pref, H), _expand_kv(k_suf, H)],
                             axis=1)
        vx = jnp.concatenate([_expand_kv(v_pref, H), _expand_kv(v_suf, H)],
                             axis=1)
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                       kx.astype(jnp.float32))
        # prefix columns: live iff < the slot's resident prefix; suffix
        # columns: plain causal (query i and key j share the pref offset)
        dead_pref = (jnp.arange(nb * bs)[None, :]
                     >= pref_lens[:, None])                   # (B, nb*bs)
        dead_suf = (jnp.arange(S)[None, :]
                    > jnp.arange(S)[:, None])                 # (S, S)
        dead = jnp.concatenate(
            [jnp.broadcast_to(dead_pref[:, None, None, :],
                              (B, 1, S, nb * bs)),
             jnp.broadcast_to(dead_suf[None, None], (B, 1, S, S))],
            axis=-1)
        s = jnp.where(dead, NEG_INF, s)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a,
                       vx.astype(jnp.float32)).astype(q.dtype)
    o = o.reshape(B, S, H * hd)
    wo = p["wo"].astype(policy.compute_dtype)
    out = quant_linear(o, wo, policy=policy)
    return out, PagedKVCache(k_buf, v_buf, cache.length)


def attention_paged_decode_step(x: Array, cache: PagedKVCache,
                                tables: Array, p: dict, cfg,
                                policy: QuantPolicy, *, rope_cache=None,
                                impl: str = "flash_scan"
                                ) -> tuple[Array, PagedKVCache]:
    """One-token decode through the block table: the slot's new KV lands
    in pool cell ``(tables[b, length[b]//bs], length[b]%bs)`` (the engine
    guarantees that block exists for live slots; idle slots' table rows
    point at the trash block) and the re-attend runs the paged decode
    kernel on the Pallas backends — per-slot lengths and the block table
    ride in as scalar-prefetch operands, dead blocks are skipped on both
    the FLOP and DMA side — or gather-then-dense on ``xla`` /
    ``impl="dense"``. Lengths advance by one for every slot, exactly like
    the ring path (idle slots decode garbage into the trash block).
    """
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bs, nb = cache.k.shape[2], tables.shape[1]
    pos = cache.length[:, None]                              # (B, 1) abs
    q, k, v = qkv_project(x, p, cfg, policy)
    if rope_cache is not None:
        q = apply_rope_cached(q, *rope_cache)
        k = apply_rope_cached(k, *rope_cache)
    else:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    logical = jnp.clip(cache.length // bs, 0, nb - 1)
    phys = jnp.take_along_axis(tables, logical[:, None], axis=1)[:, 0]
    off = cache.length % bs
    k_buf = _paged_commit(cache.k, k[:, 0], phys, off)
    v_buf = _paged_commit(cache.v, v[:, 0], phys, off)
    valid = jnp.minimum(cache.length + 1, nb * bs)           # (B,)
    backend = (policy.backend if impl != "dense"
               and policy.backend in FLASH_BACKENDS else "xla")
    o = kernel_call(
        functools.partial(PA.paged_decode_attention, backend=backend),
        q, k_buf, v_buf, tables, valid, batch_dims=(0, None, None, 0, 0),
        backend=backend)
    o = o.reshape(B, 1, H * hd)
    wo = p["wo"].astype(policy.compute_dtype)
    out = quant_linear(o, wo, policy=policy)
    return out, PagedKVCache(k_buf, v_buf, cache.length + 1)


def cross_attention(x: Array, enc_kv: tuple[Array, Array], p: dict, cfg,
                    policy: QuantPolicy, *, impl: str = "flash_scan",
                    block_q: int = 0, block_k: int = 0) -> Array:
    """Encoder-decoder cross attention; enc_kv are precomputed (B,Se,KV,hd)."""
    B, S, _ = x.shape
    wq = p["wq"].astype(policy.compute_dtype)
    q = quant_linear(x, wq, policy=policy).reshape(
        B, S, cfg.n_heads, cfg.hd)
    k, v = enc_kv
    o = _core_attention(q, k, v, causal=False, policy=policy, impl=impl,
                        block_q=block_q, block_k=block_k)
    o = o.reshape(B, S, cfg.n_heads * cfg.hd)
    wo = p["wo"].astype(policy.compute_dtype)
    return quant_linear(o, wo, policy=policy)


def encode_cross_kv(enc_out: Array, p: dict, cfg, policy: QuantPolicy):
    B, Se, _ = enc_out.shape
    wk = p["wk"].astype(policy.compute_dtype)
    k = quant_linear(enc_out, wk, policy=policy).reshape(
        B, Se, cfg.n_kv_heads, cfg.hd)
    wv = p["wv"].astype(policy.compute_dtype)
    v = quant_linear(enc_out, wv, policy=policy).reshape(
        B, Se, cfg.n_kv_heads, cfg.hd)
    return k, v
