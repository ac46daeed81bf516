"""mfu.serve: the operations the window's requests required (every linear
and the output head, two per weight for each prompt and fed-back token
and each sampled position, plus attention over each token's context),
over the window at the bf16 peak. Moves serve_tokens_per_s."""
from bench.peaks import peaks
from bench.work.serve import lm_serve


def read(ctx):
    w = lm_serve(ctx.cell.config, ctx.requests)
    return 100.0 * w["ops"] / (ctx.window_s
                               * peaks(ctx.device["kind"])["bf16_flops"])
