"""switchback_roofline: the least time of every int8 linear's forward and
input-gradient matmul (the larger of its operations at the int8 peak and
its bytes at the HBM bandwidth, weight quantization included), over the
summed device time of the SwitchBack Pallas kernels (quantize, absmax,
fused and tiled int8 matmuls) in the traced window. Moves train_step_ms."""
from bench.peaks import peaks
from bench.work.common import least_seconds

KERNELS = (r"(tensor_quantize|row_quantize|col_quantize|int8_matmul_dequant"
           r"|fused_switchback_fwd|fused_switchback_dgrad) \(pallas\)$")


def read(ctx):
    spent = ctx.red.seconds_matching(KERNELS)
    if not spent:
        return None
    pk = peaks(ctx.device["kind"])
    i8, bw = pk["int8_ops"], pk["hbm_bytes_per_s"]
    least = 0.0
    for ln in ctx.work[0]:
        if ln.quantized:
            least += ln.count * least_seconds(ln.matmul_ops, ln.fwd_bytes(),
                                              i8, bw)
            if ln.dgrad:
                least += ln.count * least_seconds(
                    ln.matmul_ops, ln.dgrad_bytes(), i8, bw)
    return 100.0 * least * ctx.steps / spent
