"""device_idle.train: the share of the traced window of a training cell in
which no operation ran on the device (averaged over its chips). Moves
train_step_ms."""


def read(ctx):
    return 100.0 * (1.0 - ctx.red.busy_s / ctx.red.window_s)
