"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and a compile for a
described ``v5e:2x2`` topology raises what the chip's compiler would —
misaligned blocks, scalar stores to VMEM, scoped-VMEM overflow. Shapes
are the published widths: smollm-360m (d_model 960, d_ff 2560, 15 heads
over 5 KV heads of 64, 4096 tokens) and CLIP ViT-Huge (16 text heads of
64 over 77 tokens, 16 vision heads of 80 over 129 tokens after patch
dropout).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file. Keep these tests in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import ops as FA
from repro.kernels.paged_attention import ops as PA
from repro.kernels.switchback import ops as SB

D, FF, ROWS = 960, 2560, 4096
BF, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
# (heads, kv heads, head dim, sequence)
SMOLLM = (15, 5, 64, 4096)
CLIP_TEXT = (16, 16, 64, 77)
CLIP_VISION = (16, 16, 80, 129)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


SWITCHBACK = {
    "tensor_quantize": (lambda w: SB.tensor_quantize(w, backend="pallas"),
                        [((D, FF), F32)]),
    "row_quantize": (lambda x: SB.row_quantize(x, backend="pallas"),
                     [((ROWS, D), BF)]),
    "fused_fwd": (lambda x, w, s: SB.fused_switchback_fwd(
        x, w, s, backend="pallas"),
        [((ROWS, D), BF), ((D, FF), I8), ((1, 1), F32)]),
    "fused_dgrad": (lambda g, w, s: SB.fused_switchback_dgrad(
        g, w, s, backend="pallas"),
        [((ROWS, D), BF), ((FF, D), I8), ((1, 1), F32)]),
    "wgrad_bf16": (lambda x, g: SB.wgrad_bf16(x, g, backend="pallas"),
                   [((ROWS, D), BF), ((ROWS, FF), BF)]),
}


@pytest.mark.parametrize("name", sorted(SWITCHBACK))
def test_switchback_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = SWITCHBACK[name]
    assert "tpu_custom_call" in compile_hlo(fn, one_chip, *shapes)


ATTN_SHAPES = {"smollm": SMOLLM, "clip_text": CLIP_TEXT,
               "clip_vision": CLIP_VISION}
# train calls: (batch, causal, tiles the rule picks, backward kernels):
# the benchmark's, as PERF.md's table of section 5 gives them, and a GQA
# group of 8 heads of 128 (Qwen3-MoE-30B's), whose Q tile the rule cuts to
# fit VMEM and whose resident dq (8 x 4224 rows) is past the one-pass
# kernel's budget, so it takes the split pair
FUSED, SPLIT = ["flash_bwd"], ["flash_bwd_dkv", "flash_bwd_dq"]
ATTN_CALLS = {"smollm": (4, True, (512, 1024), FUSED),
              "clip_text": (128, True, (80, 80), FUSED),
              "clip_vision": (128, False, (136, 136), FUSED),
              "gqa8_hd128": (1, True, (192, 1024), SPLIT)}
ATTN_TRAIN_SHAPES = {**ATTN_SHAPES, "gqa8_hd128": (32, 4, 128, 4096)}


def bwd_kernels(hlo):
    """Names of the backward flash kernels in a compiled fwd + grad; a
    kernel called in a transposed jvp is named after it
    (``transpose_jvp_flash_bwd__``)."""
    import re
    names = (re.fullmatch(r"(?:transpose_jvp_)?(\w+?)_*", n).group(1)
             for n, _ in custom_calls(hlo))
    return sorted(n for n in names if n.startswith("flash_bwd"))


@pytest.mark.parametrize("shape", sorted(ATTN_TRAIN_SHAPES))
def test_flash_fwd_bwd_compiles_for_v5e(shape, one_chip):
    H, KV, hd, S = ATTN_TRAIN_SHAPES[shape]
    B, causal, tiles, bwd = ATTN_CALLS[shape]
    assert FA.choose_attn_blocks(S, S, group=H // KV, hd=hd) == tiles

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: FA.flash_attention(
            q, k, v, causal=causal, backend="pallas").astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = compile_hlo(fwd_bwd, one_chip, ((B, S, H, hd), BF),
                      ((B, S, KV, hd), BF), ((B, S, KV, hd), BF))
    assert "flash_fwd" in {n for n, _ in custom_calls(hlo)}
    assert bwd_kernels(hlo) == bwd


def test_mla_flash_fwd_bwd_compiles_for_v5e(one_chip):
    """Moonlight-16B-A3B's latent attention: 16 heads, queries and keys 192
    wide, values 128 wide, 2 x 8192 tokens; the tiles ops.py records, and
    the one-pass backward with one head's 8192 x 192 dq in VMEM."""
    B, S, H, dqk, dv = 2, 8192, 16, 192, 128
    assert FA.choose_attn_blocks(S, S, group=1, hd=dqk) == (512, 1024)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: FA.flash_attention(
            q, k, v, causal=True, backend="pallas").astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = compile_hlo(fwd_bwd, one_chip, ((B, S, H, dqk), BF),
                      ((B, S, H, dqk), BF), ((B, S, H, dv), BF))
    assert "flash_fwd" in {n for n, _ in custom_calls(hlo)}
    assert bwd_kernels(hlo) == FUSED


# Moonlight's routed experts on one chip's share: 2 x 8192 tokens, top-6
# of 64 with 8 held, so the slot buffer holds T * 6 rows plus a tile of
# padding per held expert; expert width 1408 on hidden 2048
MOE_ROWS = 16384 * 6 + 8 * SB.MOE_TILE_ROWS
MOE_TILES = MOE_ROWS // SB.MOE_TILE_ROWS
MOE = {
    "moe_gmm_fwd": lambda n, m: (
        lambda x, w, s, t, c: SB.moe_gmm_fwd(
            x, w, s, t, c, block_m=SB.MOE_TILE_ROWS, backend="pallas"),
        [((MOE_ROWS, n), BF), ((8, n, m), I8), ((8, 1, 1), F32),
         ((MOE_TILES,), I32), ((1,), I32)]),
    "moe_gmm_dgrad": lambda n, m: (
        lambda g, w, s, t, c: SB.moe_gmm_dgrad(
            g, w, s, t, c, block_m=SB.MOE_TILE_ROWS, backend="pallas"),
        [((MOE_ROWS, m), BF), ((8, n, m), I8), ((8, 1, 1), F32),
         ((MOE_TILES,), I32), ((1,), I32)]),
    "moe_gmm_wgrad": lambda n, m: (
        lambda x, g, t, c: SB.moe_gmm_wgrad(
            x, g, t, c, block_m=SB.MOE_TILE_ROWS, n_experts=8,
            backend="pallas"),
        [((MOE_ROWS, n), BF), ((MOE_ROWS, m), BF), ((MOE_TILES,), I32),
         ((1,), I32)]),
}


@pytest.mark.parametrize("nm", [(2048, 1408), (1408, 2048)],
                         ids=["up", "down"])
@pytest.mark.parametrize("name", sorted(MOE))
def test_moe_grouped_kernel_compiles_for_v5e(name, nm, one_chip):
    fn, shapes = MOE[name](*nm)
    hlo = compile_hlo(fn, one_chip, *shapes)
    assert [n for n, _ in custom_calls(hlo)] == [name]


@pytest.mark.parametrize("shape", ["smollm", "clip_text"])
def test_flash_decode_compiles_for_v5e(shape, one_chip):
    H, KV, hd, _ = ATTN_SHAPES[shape]
    B, S_max = 8, 1024
    hlo = compile_hlo(
        lambda q, k, v, n: FA.decode_attention(q, k, v, n, backend="pallas"),
        one_chip, ((B, 1, H, hd), BF), ((B, KV, S_max, hd), BF),
        ((B, KV, S_max, hd), BF), ((B,), I32))
    assert "tpu_custom_call" in hlo


def _pool(KV, hd, N=512, bs=16):
    return ((N + 1, KV, bs, hd), BF)


def test_paged_decode_compiles_for_v5e(one_chip):
    H, KV, hd, _ = SMOLLM
    B, nb = 8, 64
    hlo = compile_hlo(
        lambda q, k, v, t, n: PA.paged_decode_attention(
            q, k, v, t, n, backend="pallas"),
        one_chip, ((B, 1, H, hd), BF), _pool(KV, hd), _pool(KV, hd),
        ((B, nb), I32), ((B,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("S", [256, 5])      # prefill chunk, spec verify
def test_paged_prefill_compiles_for_v5e(S, one_chip):
    H, KV, hd, _ = SMOLLM
    B, nb = 8, 64
    hlo = compile_hlo(
        lambda q, k, v, t, o, n: PA.paged_prefill_attention(
            q, k, v, t, o, n, backend="pallas"),
        one_chip, ((B, S, H, hd), BF), _pool(KV, hd), _pool(KV, hd),
        ((B, nb), I32), ((B,), I32), ((B,), I32))
    assert "tpu_custom_call" in hlo


# kernel names every main-path Pallas call passes (``name=``): the device
# trace groups its time under these, never under the function around it
KERNEL_NAMES = {"flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv",
                "flash_decode", "tensor_quantize", "row_quantize",
                "col_quantize", "int8_matmul_dequant", "fused_switchback_fwd",
                "fused_switchback_dgrad", "wgrad_bf16", "paged_prefill",
                "paged_decode", "moe_gmm_fwd", "moe_gmm_dgrad",
                "moe_gmm_wgrad"}


@pytest.fixture(scope="module")
def smollm_step_hlo(topo):
    """The compiled int8 SwitchBack + Pallas train step of a smollm-360m
    cut to two layers (published widths) at 2 x 512 tokens, remat per
    layer, on one described v5e chip."""
    import dataclasses
    from jax.sharding import AxisType, Mesh
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.train import make_engine
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tc = TrainConfig(optimizer="stable_adamw", quant_mode="int8_switchback",
                     kernel_backend="pallas")
    par = ParallelConfig(mesh_shape=(1, 1), mesh_axes=("data", "model"),
                         remat="block")
    tokens = jax.ShapeDtypeStruct((2, 512), I32)
    engine = make_engine(cfg, tc, par, mesh,
                         {"tokens": tokens, "labels": tokens})
    return engine.lower().compile().as_text()


def custom_calls(hlo):
    """(instruction name without its suffix, op-name path) of every Mosaic
    kernel call in compiled HLO text."""
    import re
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.sub(r"(\.\d+)+$", "",
                          line.split(" = ", 1)[0].split()[-1].lstrip("%"))
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((name, op.group(1) if op else ""))
    return out


def test_train_step_kernels_carry_their_own_names(smollm_step_hlo):
    calls = custom_calls(smollm_step_hlo)
    names = {n for n, _ in calls}
    assert {"flash_fwd", "flash_bwd", "fused_switchback_fwd",
            "tensor_quantize"} <= names
    assert names <= KERNEL_NAMES
    assert not names & {"flash_bwd_dq", "flash_bwd_dkv"}  # dq fits VMEM
    assert not names & {"checkpoint", "pallas_call"}


def test_train_step_ops_carry_layer_scopes(smollm_step_hlo):
    """Scopes reach the compiled program as op-name metadata: the flash
    kernels and the layout moves around them sit under ``attention``, the
    SwitchBack kernels under ``linear``, the update under ``optimizer``."""
    import re
    # transforms wrap a scope: jit(train_step)/transpose(jvp(embed))/...
    parts = lambda op: set(re.split(r"[/()]", op))
    for name, op in custom_calls(smollm_step_hlo):
        scope = "attention" if name.startswith("flash_") else "linear"
        assert scope in parts(op), (name, op)
    ops = [parts(o) for o in re.findall(r'op_name="([^"]*)"',
                                        smollm_step_hlo)]
    attn = [o for o in ops if "attention" in o]
    assert any("pallas_call" not in o for o in attn)   # pads, transposes
    for scope in ("norm", "embed", "head", "optimizer"):
        assert any(scope in o for o in ops), scope


@pytest.fixture(scope="module")
def moonlight_step_hlo(topo):
    """The compiled int8 SwitchBack + Pallas train step of Moonlight-16B-A3B
    at published widths cut to its dense layer and one MoE layer with the
    8 of 64 experts the benchmark's chip holds, at 1 x 512 tokens."""
    import dataclasses
    from jax.sharding import AxisType, Mesh
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.train import make_engine
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), n_layers=2,
                              vocab_size=4096, held_experts=(0, 8))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tc = TrainConfig(optimizer="stable_adamw", quant_mode="int8_switchback",
                     kernel_backend="pallas")
    par = ParallelConfig(mesh_shape=(1, 1), mesh_axes=("data", "model"),
                         remat="block")
    tokens = jax.ShapeDtypeStruct((1, 512), I32)
    engine = make_engine(cfg, tc, par, mesh,
                         {"tokens": tokens, "labels": tokens})
    return engine.lower().compile().as_text()


def test_moonlight_step_runs_its_kernels_under_its_scopes(moonlight_step_hlo):
    """The routed experts run the ``moe_gmm_*`` kernels under
    ``moe.experts``; latent attention runs the flash kernels under ``mla``;
    the router, dispatch, shared experts and combine carry their scopes."""
    import re
    parts = lambda op: set(re.split(r"[/()]", op))
    calls = custom_calls(moonlight_step_hlo)
    names = {n for n, _ in calls}
    assert {"moe_gmm_fwd", "moe_gmm_dgrad", "moe_gmm_wgrad", "flash_fwd",
            "flash_bwd"} <= names <= KERNEL_NAMES
    assert not names & {"flash_bwd_dq", "flash_bwd_dkv"}  # dq fits VMEM
    for name, op in calls:
        if name.startswith("moe_"):
            assert "moe.experts" in parts(op), (name, op)
        if name.startswith("flash_"):
            assert {"mla", "attention"} <= parts(op), (name, op)
    ops = [parts(o) for o in re.findall(r'op_name="([^"]*)"',
                                        moonlight_step_hlo)]
    for scope in ("moe.router", "moe.dispatch", "moe.shared", "moe.combine"):
        assert any(scope in o for o in ops), scope
