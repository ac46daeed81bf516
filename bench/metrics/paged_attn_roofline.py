"""paged_attn_roofline: the least time of the window's paged attention
(prefill over each prompt, decode over each fed-back token's context; the
larger of its operations at the bf16 peak and the KV bytes it must read
at the HBM bandwidth), over the summed device time of the paged-attention
Pallas kernels in the traced window. Moves serve_tokens_per_s."""
from bench.peaks import peaks
from bench.work.serve import lm_serve

KERNELS = r"paged_\w+ \(pallas\)$"


def read(ctx):
    spent = ctx.red.seconds_matching(KERNELS)
    if not spent:
        return None
    pk = peaks(ctx.device["kind"])
    w = lm_serve(ctx.cell.config, ctx.requests)
    least = sum(max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
                for ops, nbytes in w["attention"])
    return 100.0 * least / spent
