"""flash_attn_roofline: the least time of every attention call's forward
and backward pass (the larger of its required operations at the bf16 peak
and its bytes at the HBM bandwidth), over the summed device time of the
flash-attention Pallas kernels in the traced window. Moves
train_step_ms."""
from bench.peaks import peaks
from bench.work.common import least_seconds

# The forward kernels carry the name of their wrapper (flash_fwd_lse);
# the backward kernels, called from the custom VJP without a wrapper of
# their own, carry the name of the rematerialised block around them.
KERNELS = r"(flash_\w+|checkpoint) \(pallas\)$"


def read(ctx):
    spent = ctx.red.seconds_matching(KERNELS)
    if not spent:
        return None
    pk = peaks(ctx.device["kind"])
    bf, bw = pk["bf16_flops"], pk["hbm_bytes_per_s"]
    least = sum(a.count * (least_seconds(a.fwd_ops(), a.fwd_bytes(), bf, bw)
                           + least_seconds(a.bwd_ops(), a.bwd_bytes(), bf, bw))
                for a in ctx.work[1])
    return 100.0 * least * ctx.steps / spent
