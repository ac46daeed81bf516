"""device_idle.serve: the share of the traced window of a serving cell in
which no operation ran on the device (averaged over its chips). Moves
serve_tokens_per_s."""


def read(ctx):
    return 100.0 * (1.0 - ctx.red.busy_s / ctx.red.window_s)
