"""The latent-attention / mixture-of-experts cell at a size the CPU holds:
its reference against the program's parameter tree, a whole run of the
train driver that passes, the faults and the int4 control that fail under
the cell's own limits, its work counts by hand and ``moe_gmm_roofline``
on a hand-made trace reduction."""
import copy
import json
import time
import types

import jax
import pytest

from bench import common, trace
from bench.peaks import PEAKS
from bench.work import mla_moe
from bench.work.common import least_seconds, mfu_seconds

CELL = "moonlight-16b-a3b-ep8.train-int8"
# widths of the program's reduced configuration (moonlight-16b-a3b-reduced)
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, num_experts_per_tok=3, n_shared_experts=2,
            num_hidden_layers=2, first_k_dense_replace=1, vocab_size=256)


def tiny_cell():
    cell = copy.deepcopy(common.load_cell(CELL))
    c = cell.config
    c.update(TINY)
    c["share"]["router_experts"] = 16
    c["path"]["kernel_backend"] = "xla"
    c["program"] = {"arch": "moonlight-16b-a3b-reduced", "replace": {}}
    cell.traffic.update(batch=2, seq=64, ring=4)
    return cell


# The cell's limits are set at its own widths on the chip. At 64 features
# and 128 tokens per step bf16 rounding and a single routing flip among
# 384 slots move a step's loss by up to 2e-3 (the reference itself with
# int8 linears reads 6e-4 to 2.1e-3 over seeds 9-11) and grad1_gap by up to
# 0.03, while the int4 control reads 7.7e-3 to 2.3e-2 and 0.14 to 0.21 and
# a lost update reads delta_gap 1: these limits lie between.
TINY_LIMITS = {"loss_gap": 0.005, "grad1_gap": 0.08, "delta_gap": 0.05}


def run(cell, seed=2 ** 33 + 11):
    return json.loads(cell.driver.run(cell, seed, 0.5, False,
                                      jax.devices()[:1], time.perf_counter(),
                                      limits=TINY_LIMITS))


def plant_step(monkeypatch, fault):
    import repro.train.engine as eng
    real = eng.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: fault(step, state, batch)

    monkeypatch.setattr(eng, "make_train_step", make)


def test_reference_tree_is_the_programs():
    from bench.drivers import train as T
    from repro.models import build
    from repro.models.params import abstract_params
    for cell in (tiny_cell(), common.load_cell(CELL)):
        prog = abstract_params(build(T.program_config(cell.config))
                               .param_specs)
        assert T.shapes(prog) == T.shapes(cell.reference.abstract(
            cell.config))


def test_published_widths_and_the_cut():
    """The file keeps the catalog's widths; only depth, experts held and
    the vocabulary are cut, with the published values beside them."""
    from bench.drivers import train as T
    c = common.load_cell(CELL).config
    cfg = T.program_config(c)
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff) == (2048, 16, 11264)
    assert (cfg.mla.kv_lora_rank, cfg.mla.qk_nope_dim, cfg.mla.qk_rope_dim,
            cfg.mla.v_dim) == (512, 128, 64, 128)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.expert_ff,
            cfg.moe.n_shared) == (64, 6, 1408, 2)
    assert cfg.held == (0, c["n_routed_experts"]) == (0, 8)
    assert (cfg.n_layers, cfg.vocab_size) == (c["num_hidden_layers"],
                                              c["vocab_size"])
    assert c["published"] == {"num_hidden_layers": 27,
                              "n_routed_experts": 64, "vocab_size": 163840}
    assert c["share"]["router_experts"] == 64


def test_sound_run_is_correct():
    out = run(tiny_cell())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_unchanged_state_fails(monkeypatch):
    plant_step(monkeypatch, lambda step, s, b: (s, step(s, b)[1]))
    out = run(tiny_cell())
    assert not out["correct"]
    assert out["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_and_int4_control_fail():
    from bench import weights
    from bench.drivers import train as T
    from bench.reference import common as refc
    cell = tiny_cell()
    abstract = cell.reference.abstract(cell.config)
    make_w = jax.jit(lambda k: weights.make(abstract, k))
    ring = cell.kind.make(cell.traffic, cell.config, 9)
    wkey, dkey = jax.random.split(common.raw_key(9))
    ref = T.reference_readings(cell, make_w, ring, wkey, dkey)
    lim = TINY_LIMITS
    for kw in ({"bits": 4}, {"rows": slice(0, 1)}):
        gaps = refc.compare_train(T.reference_readings(
            cell, make_w, ring, wkey, dkey, **kw), ref)
        assert any(gaps[k] > lim[k] for k in lim), (kw, gaps)


def test_work_counts_by_hand():
    a = mla_moe.LatentAttention(batch=1, sq=4, sk=4, heads=2, kv_heads=2,
                                head_dim=6, causal=True, v_dim=4)
    assert a.fwd_ops() == 2 * 1 * 2 * 10 * (6 + 4)      # q.k at 6, p.v at 4
    assert a.bwd_ops() == 2 * a.fwd_ops()
    assert a.fwd_bytes() == 2 * (4 * 2 * 10 * 2) + 1 * 2 * 4 * 4
    ex = mla_moe.RoutedExperts(slots=12, n=4, m=3, experts=2)
    assert ex.matmul_ops == 2 * 12 * 4 * 3
    assert ex.fwd_bytes() == 2 * 12 * 7 + 2 * 12
    assert ex.wgrad_bytes() == 2 * 12 * 7 + 4 * 2 * 12
    c = common.load_cell(CELL).config
    linears, attns = mla_moe.train_step(c, {"batch": 2, "seq": 8192})
    routed = [x for x in linears if isinstance(x, mla_moe.RoutedExperts)]
    assert [(x.n, x.m, x.count) for x in routed] == [
        (2048, 1408, 5), (2048, 1408, 5), (1408, 2048, 5)]
    assert routed[0].slots == 16384 * 6 * 8 / 64        # 1536 per expert
    fwd = (sum(x.count * x.matmul_ops for x in linears)
           + sum(x.count * x.fwd_ops() for x in attns))
    assert 3 * fwd == pytest.approx(43.17e12, rel=1e-3)  # ISSUE's 43 TFLOP
    pk = PEAKS["TPU v5 lite"]
    assert mfu_seconds(linears, attns, pk) == pytest.approx(0.1742, rel=1e-3)


def test_moe_gmm_roofline_reads_the_moe_kernels():
    mod = common.load_module(common.BENCH / "metrics"
                             / "moe_gmm_roofline.py")
    ex = mla_moe.RoutedExperts(slots=1e6, n=2048, m=1408, experts=8,
                               count=2)
    pk = PEAKS["TPU v5 lite"]
    least = 2 * (2 * least_seconds(ex.matmul_ops, ex.fwd_bytes(),
                                   pk["int8_ops"], pk["hbm_bytes_per_s"])
                 + least_seconds(ex.matmul_ops, ex.wgrad_bytes(),
                                 pk["bf16_flops"], pk["hbm_bytes_per_s"]))
    ops = {"moe_gmm_fwd (pallas)": 0.5, "moe_gmm_wgrad (pallas)": 0.5,
           "fused_switchback_fwd (pallas)": 7.0, "fusion.3 (loop)": 2.0}
    red = trace.Reduced(window_s=10.0, busy_s=9.0, ops=ops, gaps=[])
    ctx = types.SimpleNamespace(red=red, steps=3, work=([ex], []),
                                device={"kind": "TPU v5 lite"})
    assert mod.read(ctx) == pytest.approx(100.0 * least * 3 / 1.0)
    red.ops = {"fused_switchback_fwd (pallas)": 7.0}
    assert mod.read(ctx) is None


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    """A ``--trace 1`` run with the chip's trace stood in for by the
    recorded one (which holds no ``moe_`` kernel): the line carries the
    cell's metrics that find something to read, and the work module loads
    the way the driver loads it."""
    from test_bench_trace import profile
    red = trace.reduce_profile(profile()[0])
    monkeypatch.setattr(trace, "start", lambda: "unused")
    monkeypatch.setattr(trace, "stop", lambda: None)
    monkeypatch.setattr(trace, "reduce_dir", lambda d: red)
    monkeypatch.setattr(trace, "cleanup", lambda d: None)
    info = common.device_info
    monkeypatch.setattr(common, "device_info", lambda devs: dict(
        info(devs), kind="TPU v5 lite"))
    cell = tiny_cell()
    out = json.loads(cell.driver.run(cell, 7, 0.5, True, jax.devices()[:1],
                                     time.perf_counter(),
                                     limits=TINY_LIMITS))
    assert {"mfu.train", "flash_attn_roofline", "switchback_roofline",
            "device_idle.train"} <= set(out["metrics"])
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "moe_gmm_roofline" in {m["name"] for m in cell.per_layer}
