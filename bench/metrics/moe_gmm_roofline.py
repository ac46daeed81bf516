"""moe_gmm_roofline: the least time of the routed experts' three grouped
matmuls (forward and input gradient at the int8 peak, weight gradient at
the bf16 peak, or their bytes at the HBM bandwidth, whichever is larger),
over the summed device time of the ``moe_`` Pallas kernels in the traced
window. None where no such kernel ran. Moves train_step_ms."""
from bench.peaks import peaks
from bench.work.common import least_seconds

KERNELS = r"moe_\w+ \(pallas\)$"


def read(ctx):
    spent = ctx.red.seconds_matching(KERNELS)
    if not spent:
        return None
    pk = peaks(ctx.device["kind"])
    i8, bf, bw = pk["int8_ops"], pk["bf16_flops"], pk["hbm_bytes_per_s"]
    least = 0.0
    for ex in ctx.work[0]:
        if hasattr(ex, "wgrad_bytes"):
            least += ex.count * (
                least_seconds(ex.matmul_ops, ex.fwd_bytes(), i8, bw)
                + least_seconds(ex.matmul_ops, ex.dgrad_bytes(), i8, bw)
                + least_seconds(ex.matmul_ops, ex.wgrad_bytes(), bf, bw))
    return 100.0 * least * ctx.steps / spent
