"""Each metric's work function against hand counts at one small shape,
and each per-layer reader on a hand-made trace reduction."""
import types

import pytest

from bench import common, trace
from bench.peaks import PEAKS, peaks
from bench.work import clip, lm
from bench.work.common import Attention, Linear, least_seconds, mfu_seconds
from bench.work.serve import lm_serve

V5E = "TPU v5 lite"
PK = PEAKS[V5E]
LM = dict(hidden_size=4, num_attention_heads=2, num_key_value_heads=1,
          intermediate_size=8, num_hidden_layers=3, vocab_size=16)


def metric(name):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


def test_linear_and_attention_counts():
    ln = Linear(tokens=8, n=4, m=3, quantized=True, dgrad=True)
    assert ln.matmul_ops == 2 * 8 * 4 * 3
    assert ln.fwd_bytes() == 2 * 8 * 4 + 2 * 8 * 3 + 4 * 4 * 3
    assert ln.dgrad_bytes() == 2 * 8 * 3 + 2 * 8 * 4 + 4 * 3
    a = Attention(batch=1, sq=4, sk=4, heads=2, kv_heads=1, head_dim=8,
                  causal=True)
    assert a.pairs == 10                       # 1 + 2 + 3 + 4
    assert a.fwd_ops() == 2 * (2 * 2 * 10 * 8)  # QK^T and PV, 2 heads
    assert a.bwd_ops() == 2 * a.fwd_ops()       # dV, dP, dQ, dK
    q, kv = 4 * 2 * 8 * 2, 4 * 1 * 8 * 2        # bf16 bytes
    assert a.fwd_bytes() == 2 * q + 2 * kv + 2 * 4 * 4   # + f32 lse
    assert Attention(1, 3, 5, 1, 1, 2, causal=False).pairs == 15
    assert least_seconds(10.0, 100.0, 10.0, 10.0) == 10.0


def test_lm_train_step_shapes():
    linears, attns = lm.train_step(LM, {"batch": 2, "seq": 5})
    T = 10
    q = [(ln.n, ln.m, ln.count) for ln in linears if ln.quantized]
    assert q == [(4, 4, 3), (4, 2, 3), (4, 2, 3), (4, 4, 3), (4, 8, 3),
                 (4, 8, 3), (8, 4, 3)]
    assert all(ln.tokens == T for ln in linears)
    head = [ln for ln in linears if not ln.quantized]
    assert [(h.n, h.m) for h in head] == [(4, 16)]
    assert attns == [Attention(2, 5, 5, 2, 1, 2, causal=True, count=3)]


def test_mfu_seconds_by_hand():
    lin = [Linear(10, 4, 4, quantized=True, dgrad=True, count=2),
           Linear(10, 4, 16, quantized=False, dgrad=False)]
    att = [Attention(1, 4, 4, 2, 2, 8, causal=False)]
    pk = {"int8_ops": 4.0, "bf16_flops": 2.0}
    want = (2 * 320 * (2 / 4 + 1 / 2)           # fwd+dgrad int8, wgrad bf16
            + 1280 * (1 / 2 + 1 / 2)            # head fwd + wgrad at bf16
            + 3 * (2 * 2 * 2 * 16 * 8) / 2)     # attention fwd + bwd
    assert mfu_seconds(lin, att, pk) == pytest.approx(want)


def test_clip_train_step_shapes():
    c = {"embed_dim": 6, "patch_dropout": 0.5,
         "vision_cfg": {"image_size": 8, "patch_size": 2, "width": 4,
                        "head_width": 2, "layers": 2, "mlp_ratio": 4},
         "text_cfg": {"context_length": 5, "vocab_size": 9, "width": 4,
                      "heads": 2, "layers": 1, "mlp_ratio": 2}}
    linears, attns = clip.train_step(c, {"batch": 3})
    patch = linears[0]
    assert (patch.tokens, patch.n, patch.m, patch.dgrad) == (48, 12, 4, False)
    vision = [ln for ln in linears[1:8]]
    assert [ln.tokens for ln in vision] == [27] * 6 + [3]   # 8 kept + cls
    assert attns == [Attention(3, 9, 9, 2, 2, 2, False, 2),
                     Attention(3, 5, 5, 2, 2, 2, True, 1)]


def test_lm_serve_by_hand():
    w = lm_serve(LM, [(3, 2)])
    per_tok = 2 * 3 * (4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8)
    fed, head = 3 + 1, 2 * 4 * 16 * 2
    pairs = 6 + 4                               # prefill 1+2+3, decode ctx 4
    assert w["ops"] == per_tok * fed + head + 3 * 4 * 2 * 2 * pairs
    kv = 3 * 2 * 1 * 2 * 2
    assert w["attention"] == [(3 * 16 * 6, kv * 3), (3 * 16 * 4, kv * 4)]


def reduced(ops, busy=1.0, window=2.0):
    return trace.Reduced(window_s=window, busy_s=busy, ops=ops, gaps=[])


def ctx(**kw):
    return types.SimpleNamespace(device={"kind": V5E}, **kw)


def test_train_readers():
    work = lm.train_step(LM, {"batch": 2, "seq": 5})
    c = ctx(work=work, step_s=0.5, steps=4,
            red=reduced({"fused_switchback_fwd (pallas)": 0.1,
                         "checkpoint (pallas)": 0.2,
                         "flash_fwd_lse (pallas)": 0.2, "fusion:kLoop": 5.0}))
    assert metric("mfu.train").read(c) == pytest.approx(
        100 * mfu_seconds(*work, PK) / 0.5)
    i8, bw = PK["int8_ops"], PK["hbm_bytes_per_s"]
    least = sum(ln.count * (least_seconds(ln.matmul_ops, ln.fwd_bytes(),
                                          i8, bw)
                            + least_seconds(ln.matmul_ops, ln.dgrad_bytes(),
                                            i8, bw))
                for ln in work[0] if ln.quantized)
    assert metric("switchback_roofline").read(c) == pytest.approx(
        100 * least * 4 / 0.1)
    a = work[1][0]
    bf = PK["bf16_flops"]
    least = a.count * (least_seconds(a.fwd_ops(), a.fwd_bytes(), bf, bw)
                       + least_seconds(a.bwd_ops(), a.bwd_bytes(), bf, bw))
    assert metric("flash_attn_roofline").read(c) == pytest.approx(
        100 * least * 4 / 0.4)
    assert metric("device_idle.train").read(c) == pytest.approx(50.0)
    c.red = reduced({"fusion:kLoop": 1.0})
    assert metric("switchback_roofline").read(c) is None
    assert metric("flash_attn_roofline").read(c) is None


def test_serve_readers():
    reqs = [(3, 2), (5, 1)]
    w = lm_serve(LM, reqs)
    c = ctx(cell=types.SimpleNamespace(config=LM), requests=reqs,
            window_s=2.0, red=reduced({"paged_decode_fwd (pallas)": 1e-6,
                                       "paged_prefill_fwd (pallas)": 1e-6},
                                      busy=0.5))
    assert metric("mfu.serve").read(c) == pytest.approx(
        100 * w["ops"] / (2.0 * PK["bf16_flops"]))
    least = sum(max(o / PK["bf16_flops"], b / PK["hbm_bytes_per_s"])
                for o, b in w["attention"])
    assert metric("paged_attn_roofline").read(c) == pytest.approx(
        100 * least / 2e-6)
    assert metric("device_idle.serve").read(c) == pytest.approx(75.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v4")
