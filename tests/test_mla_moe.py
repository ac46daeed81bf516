"""Latent attention and the dropless held-experts MoE layer against the
plain reference (``bench/reference/mla_moe.py``) at a small
Moonlight-shaped configuration (``moonlight-16b-a3b-reduced``: one dense
layer, one MoE layer of 4 held experts of 16, top-3, two shared experts),
and the pieces under them: flash attention at dv != dqk, the grouped
SwitchBack kernels, the chip's share, droplessness and the routing
counters."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.core import switchback as SB
from repro.core.precision import QuantPolicy
from repro.kernels.flash_attention import ops as FA
from repro.kernels.switchback import ops as KOPS
from repro.kernels.switchback import ref as KREF
from repro.models import attention as ATT
from repro.models import build
from repro.models import moe as MOE
from repro.models import transformer as TF

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import weights  # noqa: E402
from bench.reference import mla_moe as R  # noqa: E402

CFG = get_config("moonlight-16b-a3b-reduced")
PAR = ParallelConfig(remat="block")
KEY = jax.random.PRNGKey(0)


def ref_config(cfg):
    """The reference's configuration keys for a program config."""
    m, e = cfg.mla, cfg.moe
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": m.kv_lora_rank, "qk_nope_head_dim": m.qk_nope_dim,
            "qk_rope_head_dim": m.qk_rope_dim, "v_head_dim": m.v_dim,
            "vocab_size": cfg.vocab_size,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_hidden_layers": cfg.n_layers,
            "n_routed_experts": cfg.held[1],
            "moe_intermediate_size": e.expert_ff,
            "intermediate_size": cfg.d_ff, "n_shared_experts": e.n_shared,
            "num_experts_per_tok": e.top_k, "norm_topk_prob": e.norm_topk,
            "routed_scaling_factor": e.routed_scale,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "seq_aux_alpha": e.balance_alpha,
            "share": {"router_experts": e.n_experts,
                      "held_first": cfg.held[0]}}


def batch(S=32, B=2, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0,
                             CFG.vocab_size)
    return {"tokens": tok[:, :-1], "labels": tok[:, 1:]}


def gaps(prog, ref):
    """Each leaf's gap of gradient norms over the larger of its reference
    norm and the median leaf's (the benchmark's grad1_gap rule)."""
    n = lambda g: {jax.tree_util.keystr(k): float(jnp.linalg.norm(v))
                   for k, v in jax.tree_util.tree_flatten_with_path(g)[0]}
    p, r = n(prog), n(ref)
    med = float(np.median(list(r.values())))
    return max(abs(p[k] - r[k]) / max(r[k], med) for k in r)


@pytest.fixture(scope="module")
def setup():
    c = ref_config(CFG)
    abstract = R.abstract(c)
    params = weights.make(abstract, jax.random.PRNGKey(3))
    b = batch()
    ref_loss, ref_grad = jax.jit(jax.value_and_grad(
        lambda p: R.loss(p, b, None, c)))(params)
    x, _ = jax.jit(lambda p: R.hidden(p, b["tokens"], c))(params)
    ref_logits = jnp.einsum("bsd,dv->bsv", x, params["head"],
                            precision=jax.lax.Precision.HIGHEST)
    return c, params, b, float(ref_loss), ref_grad, ref_logits


# (mode, backend, loss, logits, gradient tolerance); logits by the norm
# of their difference over the reference's. fp32: the program is the
# reference's arithmetic in another order (reads 2e-7). bf16: activations
# rounded to 8 bits of mantissa (2^-8 = 4e-3 per op) read 1.4e-2 on the
# logits, 4e-4 on the loss. int8: SwitchBack's 127-level row/tensor-wise
# quantization at 64 features adds ~1% per matmul and flips the routing
# of a few tokens at top-3 near-ties, whose logits then move wholesale
# (one by 59%): 9% on the logits, 2.8e-3 on the loss, 3% on the worst
# leaf's gradient. The pallas backend runs the flash and grouped kernels
# in interpret mode and reads the xla backend's numbers.
CASES = [("fp32", "xla", 1e-5, 1e-5, 1e-5),
         ("bf16", "xla", 2e-3, 0.03, 0.02),
         ("int8_switchback", "xla", 6e-3, 0.15, 0.06),
         ("int8_switchback", "pallas_interpret", 6e-3, 0.15, 0.06)]


@pytest.mark.parametrize("mode,backend,tol_loss,tol_logit,tol_grad", CASES)
def test_program_follows_the_reference(setup, mode, backend, tol_loss,
                                       tol_logit, tol_grad):
    c, params, b, ref_loss, ref_grad, ref_logits = setup
    pol = QuantPolicy(mode, backend=backend, compute_dtype=(
        jnp.float32 if mode == "fp32" else jnp.bfloat16))
    bundle = build(CFG)
    (loss, metrics), grad = jax.jit(jax.value_and_grad(
        lambda p: bundle.loss_fn(p, b, pol, PAR), has_aux=True))(params)
    logits, _ = jax.jit(lambda p: TF.forward(p, b["tokens"], CFG, pol,
                                             PAR))(params)
    assert abs(float(loss) - ref_loss) / ref_loss < tol_loss
    diff = logits.astype(jnp.float32) - ref_logits
    assert float(jnp.linalg.norm(diff) / jnp.linalg.norm(ref_logits)
                 ) < tol_logit
    assert gaps(grad, ref_grad) < tol_grad
    assert float(jnp.max(jnp.abs(grad["blocks"]["pos0"]["moe"]["bias"]))
                 ) == 0.0                      # selection only: not trained


# (B, S, H, KV, dqk, dv, causal, block_q, block_k): MLA's 3:2 widths with
# one tile and with several (padding, online-softmax rescale across KV
# tiles, causal skip), GQA at dv != dqk, and non-causal
DV_CASES = [(2, 40, 4, 4, 24, 16, True, 0, 0),
            (1, 37, 2, 2, 48, 32, True, 16, 8),
            (2, 24, 4, 2, 24, 16, True, 8, 16),
            (1, 19, 2, 1, 16, 40, False, 8, 8)]


@pytest.mark.parametrize("case", DV_CASES)
def test_flash_attention_at_dv_other_than_dqk(case):
    B, S, H, KV, dqk, dv, causal, bq, bk = case
    k = jax.random.split(KEY, 4)
    q = jax.random.normal(k[0], (B, S, H, dqk))
    kk = jax.random.normal(k[1], (B, S, KV, dqk))
    v = jax.random.normal(k[2], (B, S, KV, dv))
    do = jax.random.normal(k[3], (B, S, H, dv))
    dense = lambda q, kk, v: ATT.dense_attention(
        q, ATT._expand_kv(kk, H), ATT._expand_kv(v, H), causal=causal)
    o_ref, vjp_ref = jax.vjp(dense, q, kk, v)
    o, vjp = jax.vjp(lambda q, kk, v: FA.flash_attention(
        q, kk, v, causal=causal, backend="pallas_interpret", block_q=bq,
        block_k=bk), q, kk, v)
    assert o.shape == (B, S, H, dv)
    # f32 throughout: only the softmax's reassociation differs
    np.testing.assert_allclose(o, o_ref, atol=1e-4, rtol=1e-4)
    for a, r in zip(vjp(do), vjp_ref(do)):
        np.testing.assert_allclose(a, r, atol=2e-4, rtol=2e-4)


def test_flash_shape_check_rejects_mismatched_batch_or_keys():
    q = jnp.zeros((2, 8, 4, 24))
    with pytest.raises(ValueError):
        FA.flash_fwd_lse(q, jnp.zeros((1, 8, 4, 24)), jnp.zeros((1, 8, 4, 16)),
                         causal=True)
    with pytest.raises(ValueError):
        FA.flash_fwd_lse(q, jnp.zeros((2, 8, 4, 24)), jnp.zeros((2, 7, 4, 16)),
                         causal=True)
    with pytest.raises(ValueError):
        FA.flash_fwd_lse(q, jnp.zeros((2, 8, 4, 16)), jnp.zeros((2, 8, 4, 16)),
                         causal=True)


# expert of every slot (T tokens x k): spread, one expert empty, one with
# a single slot, and every slot on one expert (past a tile)
PLANS = {"spread": lambda T, k: jax.random.randint(KEY, (T, k), 0, 8),
         "empty_and_single": lambda T, k: jnp.concatenate(
             [jnp.full((T - 1, k), 2) + jnp.arange(k), jnp.array([[0, 6, 7]])]),
         "one_expert": lambda T, k: jnp.stack(
             [jnp.full((T,), 1), jnp.full((T,), 9), jnp.full((T,), 12)], 1)}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_grouped_kernels_match_their_oracles(plan):
    T, K, tile = 40, 3, 16
    experts = PLANS[plan](T, K).astype(jnp.int32)
    dest, row_slot, te, n_live, sizes = MOE.plan(experts, 0, 8, tile)
    held = np.asarray((experts >= 0) & (experts < 8)).reshape(-1)
    assert int(sizes.sum()) == held.sum()               # nothing dropped
    assert np.all(np.asarray(dest)[held] < row_slot.shape[0])
    live = (jnp.arange(row_slot.shape[0]) // tile) < n_live[0]
    k = jax.random.split(KEY, 3)
    x = jnp.where((row_slot >= 0)[:, None],
                  jax.random.normal(k[0], (row_slot.shape[0], 64)), 0
                  ).astype(jnp.bfloat16)
    g = jax.random.normal(k[1], (row_slot.shape[0], 48), jnp.bfloat16)
    w_q, s_w = SB.quantize_per_expert(jax.random.normal(k[2], (8, 64, 48)))
    kw = dict(block_m=tile, backend="pallas_interpret")
    y = KOPS.moe_gmm_fwd(x, w_q, s_w, te, n_live, **kw)
    dx = KOPS.moe_gmm_dgrad(g, w_q, s_w, te, n_live, **kw)
    dw = KOPS.moe_gmm_wgrad(x, g, te, n_live, n_experts=8, **kw)
    m = live[:, None]
    # the same int8 products and f32 scales: equal to bf16 output rounding
    np.testing.assert_allclose(
        jnp.where(m, y, 0).astype(jnp.float32),
        KREF.moe_gmm_fwd(x, w_q, s_w, te, n_live, block_m=tile
                         ).astype(jnp.float32), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(
        jnp.where(m, dx, 0).astype(jnp.float32),
        KREF.moe_gmm_dgrad(g, w_q, s_w, te, n_live, block_m=tile
                           ).astype(jnp.float32), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(
        dw, KREF.moe_gmm_wgrad(x, g, te, n_live, block_m=tile, n_experts=8),
        rtol=1e-5, atol=1e-4)
    empty = np.asarray(sizes) == 0
    assert np.all(np.asarray(dw)[empty] == 0)           # written, as zeros


def moe_params(params):
    return jax.tree.map(lambda p: p[0], params["blocks"]["pos0"]["moe"])


def test_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of a layer (4 of 16 experts each): their routed
    parts, with the shared experts counted once, add up to the reference
    layer that holds all 16."""
    whole = dataclasses.replace(CFG, held_experts=(0, 16))
    c = ref_config(whole)
    mp = moe_params(weights.make(R.abstract(c), jax.random.PRNGKey(5)))
    x = jax.random.normal(KEY, (2, 24, CFG.d_model))
    pol = QuantPolicy("fp32", compute_dtype=jnp.float32)
    shared = R.swiglu(x.reshape(-1, CFG.d_model), mp["shared"], R.dot)
    total = shared.reshape(x.shape)
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(CFG, held_experts=(first, 4))
        share = dict(mp, **{k: mp[k][first:first + 4]
                            for k in ("w_gate", "w_up", "w_down")})
        out, _, _ = MOE.moe_block(x, share, cfg, pol)
        total = total + out - shared.reshape(x.shape)
    ref, _ = R.moe(x, mp, c, R.dot)
    np.testing.assert_allclose(total, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode,backend", [("fp32", "xla"),
                                          ("int8_switchback",
                                           "pallas_interpret")])
def test_a_tokens_output_does_not_depend_on_its_batch(mode, backend):
    """Dropless: token 0's output is the same whatever the other tokens
    are, also when every token routes to the same held experts (the bias
    forces the worst load the slot buffer is bounded for)."""
    c = ref_config(CFG)
    mp = moe_params(weights.make(R.abstract(c), jax.random.PRNGKey(6)))
    pol = QuantPolicy(mode, backend=backend, compute_dtype=(
        jnp.float32 if mode == "fp32" else jnp.bfloat16))
    k = jax.random.split(KEY, 2)
    a = jax.random.normal(k[0], (2, 48, CFG.d_model))
    b = a.at[:, 1:].set(jax.random.normal(k[1], (2, 47, CFG.d_model)))
    crowd = dict(mp, bias=mp["bias"].at[:3].set(100.0))   # all to 0, 1, 2
    for p in (mp, crowd):
        ya, _, sa = MOE.moe_block(a, p, CFG, pol)
        yb, _, _ = MOE.moe_block(b, p, CFG, pol)
        np.testing.assert_array_equal(ya[:, 0], yb[:, 0])
    assert float(sa["moe/held_slots"]) == 2 * 48 * 3      # none dropped
    ref, _ = R.moe(a, crowd, c, R.dot)
    if mode == "fp32":
        np.testing.assert_allclose(ya, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("side", ["dispatch", "combine"])
def test_take_rows_gradient_gathers_what_a_scatter_would_add(side):
    """The dispatch (slot buffer rows read from tokens) and the combine
    (token slots read from the buffer) give the values and gradients of a
    plain masked gather, whose gradient is a scatter-add."""
    T, K, tile = 24, 3, 8
    experts = jax.random.randint(KEY, (T, K), 0, 12).astype(jnp.int32)
    dest, row_slot, _, _, _ = MOE.plan(experts, 2, 6, tile)
    R_ = row_slot.shape[0]
    row_token = jnp.where(row_slot >= 0, row_slot // K, -1)
    if side == "dispatch":
        src = jax.random.normal(KEY, (T, 16))
        idx, inv = row_token, dest.reshape(T, K)
    else:
        src = jax.random.normal(KEY, (R_, 16))
        idx, inv = dest, row_slot[:, None]

    def plain(s):
        ok = (idx >= 0) & (idx < s.shape[0])
        return jnp.where(ok[:, None], s[jnp.clip(idx, 0, s.shape[0] - 1)], 0)

    ct = jax.random.normal(jax.random.PRNGKey(1), (idx.shape[0], 16))
    y, vjp = jax.vjp(lambda s: MOE.take_rows(s, idx, inv), src)
    y0, vjp0 = jax.vjp(plain, src)
    np.testing.assert_array_equal(y, y0)
    np.testing.assert_allclose(vjp(ct)[0], vjp0(ct)[0], rtol=1e-6, atol=1e-6)


def test_slot_bound_is_every_slot_that_can_be_held():
    assert MOE.slot_bound(16384, 6, 8, 256) == 16384 * 6 + 8 * 256
    assert MOE.slot_bound(100, 6, 4, 16) == 400 + 4 * 16    # k > held
    assert MOE.slot_bound(10, 2, 8, 16) == 32 + 8 * 16


def test_routing_counters_ride_the_steps_metrics(monkeypatch):
    """The counters come back with the step's outputs, in the trainer's
    one fetch per flush: no device_get of their own."""
    from jax.sharding import AxisType
    from repro.configs.base import TrainConfig
    from repro.train import Trainer, make_engine
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    b = batch(S=16)
    engine = make_engine(CFG, TrainConfig(learning_rate=1e-3,
                                          warmup_steps=0),
                         ParallelConfig(mesh_shape=(1, 1)), mesh, b)
    trainer = Trainer(engine.step, engine.init_state(0), log_every=4)
    real, calls = jax.device_get, []
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    trainer.run(lambda i: b, 4)
    assert len(calls) == 2                   # flushes at step 0 and the end
    h = trainer.history[-1]
    assert h["moe/held_slots"] > 0
    assert h["moe/load_max_over_mean.all"] >= 1.0
    assert h["moe/load_max_over_mean.held"] >= 1.0
    assert h["moe/balance_loss"] > 0
