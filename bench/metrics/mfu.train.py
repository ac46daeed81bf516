"""mfu.train: the least time of one training step's required operations
at the chip's published peaks, over the measured step time. Forward and
input-gradient matmuls of int8 linears count at the int8 peak, all other
operations (weight gradients, attention, the output head) at the bf16
peak; recomputed operations do not count. Moves train_step_ms."""
from bench.peaks import peaks
from bench.work.common import mfu_seconds


def read(ctx):
    linears, attns = ctx.work
    return 100.0 * mfu_seconds(linears, attns,
                               peaks(ctx.device["kind"])) / ctx.step_s
