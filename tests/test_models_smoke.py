"""Per-architecture smoke tests (assignment requirement): a REDUCED config
of the same family runs one forward/train step on CPU, asserting output
shapes and no NaNs. Plus decode-vs-forward consistency for recurrent paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, get_reduced_config
from repro.configs.base import CLIPConfig, ParallelConfig
from repro.core.precision import QuantPolicy
from repro.models import build
from repro.models.params import init_params

PAR = ParallelConfig(scan_layers=True, remat="block")
POL = QuantPolicy("bf16")
KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=16):
    if isinstance(cfg, CLIPConfig):
        return {"images": jax.random.normal(
                    KEY, (B, cfg.image_size, cfg.image_size, 3), jnp.float32),
                "texts": jax.random.randint(KEY, (B, cfg.text_ctx), 0,
                                            cfg.text_vocab)}
    if cfg.family == "encdec":
        return {"frames": jax.random.normal(
                    KEY, (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16),
                "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
                "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.frontend:
        b["extra_embeds"] = jax.random.normal(
            KEY, (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return b


# the hybrid (mamba-scan) and two-tower archs compile 3-10x slower than the
# rest; keep their smoke coverage but out of the fast CI lane
_SLOW_SMOKE = ("jamba-v0.1-52b", "clip-vit-huge")


@pytest.mark.parametrize(
    "arch", [pytest.param(a, marks=pytest.mark.slow) if a in _SLOW_SMOKE
             else a for a in ALL_ARCHS])
def test_smoke_forward_and_train_step(arch, reduced):
    cfg, bundle, params = reduced(arch)
    batch = make_batch(cfg)
    # one jitted value_and_grad: an eager jax.grad here re-executes the whole
    # model op-by-op and dominated the suite's runtime
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p, b: bundle.loss_fn(p, b, POL, PAR),
        has_aux=True))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"

    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.all(np.isfinite(np.asarray(g, np.float32))), \
            f"{arch}: NaN grad at {jax.tree_util.keystr(path)}"
    # one SGD step changes params
    p2 = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                      params, grads)
    moved = any(float(jnp.max(jnp.abs(a - b))) > 0
                for a, b in zip(jax.tree.leaves(params)[:16],
                                jax.tree.leaves(p2)[:16]))
    assert moved


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_full_config_loads_and_counts(arch):
    """The FULL config builds abstract param specs of the documented size
    (no allocation — eval_shape only). Checks the configs match the
    published parameter counts to within tolerance."""
    from repro.models.params import abstract_params, is_spec
    cfg = get_config(arch)
    bundle = build(cfg)
    abstract = abstract_params(bundle.param_specs)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract))
    expected = {
        "qwen3-moe-30b-a3b": 30e9, "arctic-480b": 480e9, "rwkv6-1.6b": 1.6e9,
        "internvl2-76b": 70e9, "smollm-360m": 0.36e9, "starcoder2-3b": 3e9,
        "granite-20b": 20e9, "minitron-8b": 8e9,
        "seamless-m4t-large-v2": 2.3e9, "jamba-v0.1-52b": 52e9,
        "clip-vit-huge": 1.0e9, "moonlight-16b-a3b": 16e9,
    }[arch]
    assert 0.4 * expected < n < 2.1 * expected, \
        f"{arch}: {n/1e9:.2f}B params vs expected ~{expected/1e9:.1f}B"


@pytest.mark.slow
@pytest.mark.parametrize("arch",
                         ["smollm-360m", "rwkv6-1.6b", "jamba-v0.1-52b"])
def test_decode_matches_forward(arch):
    """Sequential decode == teacher-forced forward (exact for attention,
    recurrent states threaded correctly for ssm/hybrid)."""
    from repro.models import transformer as TF
    cfg = get_reduced_config(arch)
    pol = QuantPolicy("bf16", compute_dtype=jnp.float32)
    par = ParallelConfig(scan_layers=True, remat="none")
    params = init_params(build(cfg).param_specs, KEY)
    B, S = 2, 8
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full, _ = TF.forward(params, tokens, cfg, pol, par)
    state = TF.init_decode_state(cfg, B, 16, dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, state = TF.decode_step(params, state, tokens[:, t:t + 1],
                                   cfg, pol, par)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_scan_equals_unroll():
    """scan_layers=True and False compute the same function."""
    from repro.models import transformer as TF
    cfg = get_reduced_config("smollm-360m")
    pol = QuantPolicy("bf16", compute_dtype=jnp.float32)
    params = init_params(build(cfg).param_specs, KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    a, _ = TF.forward(params, tokens, cfg, pol,
                      ParallelConfig(scan_layers=True, remat="none"))
    b, _ = TF.forward(params, tokens, cfg, pol,
                      ParallelConfig(scan_layers=False, remat="none"))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_remat_matches_no_remat():
    from repro.models import transformer as TF
    cfg = get_reduced_config("smollm-360m")
    pol = QuantPolicy("bf16", compute_dtype=jnp.float32)
    params = init_params(build(cfg).param_specs, KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)}

    def loss(p, par):
        return TF.loss_fn(p, batch, cfg, pol, par)[0]

    g1 = jax.jit(jax.grad(lambda p: loss(p, ParallelConfig(
        remat="none"))))(params)
    g2 = jax.jit(jax.grad(lambda p: loss(p, ParallelConfig(
        remat="block"))))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_layer_scale_zero_init_is_identity():
    """Paper §2.3: γ=0 ⇒ each block is the identity at init ⇒ feature
    magnitudes stay flat with depth."""
    import dataclasses
    from repro.models import transformer as TF
    cfg = dataclasses.replace(get_reduced_config("smollm-360m"),
                              layer_scale_init=0.0, tie_embeddings=True)
    pol = QuantPolicy("bf16", compute_dtype=jnp.float32)
    par = ParallelConfig(remat="none")
    params = init_params(build(cfg).param_specs, KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    x0 = params["embed"][tokens].astype(jnp.float32)
    # forward through blocks only: compare against pure embedding
    logits, _ = TF.forward(params, tokens, cfg, pol, par)
    # with identity blocks, logits = norm(embed) @ embed.T — recompute
    from repro.models.common import apply_norm
    xn = apply_norm(x0, params["final_norm"], cfg.norm, cfg.norm_eps)
    ref = jnp.einsum("btd,vd->btv", xn, params["embed"].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_encdec_decode_matches_forward():
    """Enc-dec (seamless): sequential decoder with self-KV cache + fixed
    cross-attention equals teacher forcing."""
    from repro.models import encdec as ED
    cfg = get_reduced_config("seamless-m4t-large-v2")
    pol = QuantPolicy("bf16", compute_dtype=jnp.float32)
    par = ParallelConfig(scan_layers=True, remat="none")
    params = init_params(build(cfg).param_specs, KEY)
    B, S = 2, 8
    frames = jax.random.normal(KEY, (B, cfg.frontend_tokens, cfg.d_model),
                               jnp.float32)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = ED.forward(params, {"frames": frames, "tokens": tokens},
                      cfg, pol, par)
    st = ED.init_decode_state(params, frames, cfg, pol, par, B, 16,
                              dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, st = ED.decode_step(params, st, tokens[:, t:t + 1], cfg, pol, par)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_linear_weights_are_cast_to_compute_dtype_before_use():
    """A linear casts its f32 master weight to the compute dtype before
    the matmul, and the int8 policy quantizes that bf16-rounded weight:
    a weight that already holds bf16 values gives bit-identical outputs."""
    from repro.models.mlp import mlp_block
    cfg = get_reduced_config("smollm-360m")
    D, F = 64, 128
    ks = jax.random.split(KEY, 4)
    p = {"w_up": jax.random.normal(ks[0], (D, F), jnp.float32),
         "w_gate": jax.random.normal(ks[1], (D, F), jnp.float32),
         "w_down": jax.random.normal(ks[2], (F, D), jnp.float32)}
    rounded = jax.tree.map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), p)
    x = jax.random.normal(ks[3], (2, 8, D), jnp.bfloat16)
    for mode in ("bf16", "int8_switchback"):
        pol = QuantPolicy(mode)
        out = mlp_block(x, p, cfg, pol)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(mlp_block(x, rounded, cfg, pol), np.float32))


@pytest.mark.slow
def test_quantized_policies_through_full_model(reduced):
    """int8-switchback and fp8 policies run end-to-end through a full
    (reduced) transformer incl. MoE experts — grads finite everywhere."""
    cfg, bundle, params = reduced("qwen3-moe-30b-a3b")
    batch = make_batch(cfg, B=2, S=16)
    for mode in ("int8_switchback", "fp8_switchback"):
        pol = QuantPolicy(mode)
        (loss, _), g = jax.jit(jax.value_and_grad(
            lambda p: bundle.loss_fn(p, batch, pol, PAR),
            has_aux=True))(params)
        assert np.isfinite(float(loss)), mode
        assert all(np.all(np.isfinite(np.asarray(x, np.float32)))
                   for x in jax.tree.leaves(g)), mode
