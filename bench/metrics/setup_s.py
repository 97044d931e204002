"""setup_s: process start to the first timed request (imports, weights
made on the device from the seed, deployment, compile or compile-cache
load, warm-up of the cell's own shapes)."""


def read(ctx):
    return ctx.setup_s
