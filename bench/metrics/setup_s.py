"""Process start to window open: imports, device start, weights,
synthesis, Stage-D executables from the compile cache, warm-up, ramp."""


def read(ctx):
    return ctx.setup_s
