"""Units for the distributed substrate: HLO collective parsing, the
roofline model, sharding rules."""
import numpy as np
import pytest

from repro.distributed.hlo_analysis import (collective_summary,
                                            count_dot_flops_by_dtype,
                                            parse_collectives)
from repro.distributed.roofline import (RooflineCell, model_flops,
                                        PEAK_BF16, PEAK_INT8)


HLO_SAMPLE = """
HloModule test
fused {
  %p = bf16[128,256]{1,0} parameter(0)
}
ENTRY main {
  %a = bf16[128,256]{1,0} parameter(0)
  %ag = bf16[2048,256]{1,0} all-gather(%a), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[512,512]{1,0} all-reduce(%b), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = bf16[64,256]{1,0} reduce-scatter(%c), replica_groups=[32,8]<=[256], dimensions={0}
  %cp = bf16[8,128]{1,0} collective-permute(%d), source_target_pairs={{0,1}}
  %w = s8[64,128]{1,0} parameter(1)
  %x = s8[32,64]{1,0} parameter(2)
  %dot1 = s32[32,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %y = bf16[32,64]{1,0} parameter(3)
  %z = bf16[64,16]{1,0} parameter(4)
  %dot2 = f32[32,16]{1,0} dot(%y, %z), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


class TestHLOParsing:
    def test_parse_collectives_kinds_and_groups(self):
        ops = parse_collectives(HLO_SAMPLE, 256)
        kinds = sorted(o.kind for o in ops)
        assert kinds == ["all-gather", "all-reduce", "collective-permute",
                         "reduce-scatter"]
        ag = next(o for o in ops if o.kind == "all-gather")
        assert ag.group_size == 16
        assert ag.bytes == 2048 * 256 * 2
        ar = next(o for o in ops if o.kind == "all-reduce")
        assert ar.group_size == 4
        assert ar.bytes == 512 * 512 * 4

    def test_wire_byte_factors(self):
        ops = {o.kind: o for o in parse_collectives(HLO_SAMPLE, 256)}
        ag = ops["all-gather"]
        np.testing.assert_allclose(ag.wire_bytes_per_device,
                                   (15 / 16) * ag.bytes)
        ar = ops["all-reduce"]
        np.testing.assert_allclose(ar.wire_bytes_per_device,
                                   2 * (3 / 4) * ar.bytes)
        cp = ops["collective-permute"]
        np.testing.assert_allclose(cp.wire_bytes_per_device, cp.bytes)

    def test_dot_flops_classification(self):
        d = count_dot_flops_by_dtype(HLO_SAMPLE)
        assert d["int8"] == 2 * 32 * 64 * 128      # s32 result => int8 dot
        assert d["other"] == 2 * 32 * 64 * 16

    def test_summary_totals(self):
        s = collective_summary(HLO_SAMPLE, 256)
        assert s["n_ops"] == 4
        assert s["wire_bytes_per_device"] > 0


class TestRooflineModel:
    def _cell(self, **kw):
        base = dict(arch="a", shape="train_4k", mesh="16x16", n_devices=256,
                    flops_int8=0.0, flops_other=197e12, bytes_accessed=819e9,
                    wire_bytes=50e9, model_flops_global=197e12 * 256)
        base.update(kw)
        return RooflineCell(**base)

    def test_terms_are_seconds(self):
        c = self._cell()
        assert c.t_compute == pytest.approx(1.0)
        assert c.t_memory == pytest.approx(1.0)
        assert c.t_collective == pytest.approx(1.0)

    def test_int8_credited_at_2x(self):
        c = self._cell(flops_other=0.0, flops_int8=PEAK_INT8)
        assert c.t_compute == pytest.approx(1.0)
        c2 = self._cell(flops_other=0.0, flops_int8=PEAK_BF16)
        assert c2.t_compute == pytest.approx(0.5)

    def test_bottleneck_and_fraction(self):
        c = self._cell(wire_bytes=500e9)
        assert c.bottleneck == "collective"
        assert c.roofline_fraction == pytest.approx(0.1)
        assert c.useful_ratio == pytest.approx(1.0)

    def test_model_flops_rule(self):
        assert model_flops(1e9, 1e6, "train") == 6e15
        assert model_flops(1e9, 1e6, "infer") == 2e15


class TestShardingRules:
    def test_pure_dp_folds_model_axis(self):
        from repro.configs.base import ParallelConfig
        from repro.models.params import default_rules
        par = ParallelConfig(pure_dp=True, fsdp=True)
        r = default_rules(par)
        assert r["heads"] is None and r["mlp"] is None
        assert r["batch"] == ("data", "model")
        assert r["embed"] == ("data",)

    def test_kv_head_replication_flag(self):
        from repro.configs.base import ParallelConfig
        from repro.models.params import default_rules
        assert default_rules(ParallelConfig())["kv_heads"] == "model"
        assert default_rules(
            ParallelConfig(shard_kv_heads=False))["kv_heads"] is None

    def test_duplicate_axis_dedup(self):
        from repro.models.params import logical_to_pspec
        rules = {"batch": ("data", "model"), "embed": "data"}
        ps = logical_to_pspec(("batch", "seq", "embed"), rules)
        # embed must NOT re-use 'data' (already claimed by batch)
        assert tuple(ps) == (("data", "model"), None, None)
