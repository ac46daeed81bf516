"""The logical-axis -> mesh-axis rule table (``models/params.default_rules``)
across every mesh layout and data-parallel mode the engine accepts."""
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ParallelConfig
from repro.models.params import default_rules, logical_to_pspec

MESHES = {"2d": ("data", "model"), "pod": ("pod", "data", "model")}


@pytest.mark.parametrize("mesh", sorted(MESHES))
@pytest.mark.parametrize("pure_dp", [False, True], ids=["tp", "pure_dp"])
@pytest.mark.parametrize("fsdp", [False, True], ids=["nofsdp", "fsdp"])
def test_default_rules_table(mesh, pure_dp, fsdp):
    axes = MESHES[mesh]
    data = axes[:-1]                        # ("data",) or ("pod", "data")
    r = default_rules(ParallelConfig(mesh_shape=(1,) * len(axes),
                                     mesh_axes=axes, fsdp=fsdp,
                                     pure_dp=pure_dp))
    tp = None if pure_dp else "model"
    want = {"batch": axes if pure_dp else data,
            "embed": data if fsdp else None,
            "heads": tp, "kv_heads": tp, "mlp": tp, "experts": tp,
            "vocab": tp}
    assert {k: r[k] for k in want} == want
    # a mesh axis appears once per pspec: the batch claims the data axes
    # first, so an activation's embed dim stays whole even under fsdp
    assert logical_to_pspec(("batch", "seq", "embed"), r) == \
        P(want["batch"], None, None)
