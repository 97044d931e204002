"""step_mfu (whole decode step): needed FLOPs of the window's passes over
(decode-program device time x the chip's bf16 peak), in %."""


def read(ctx):
    t = ctx.decode_device_s()
    if not t or ctx.work.passes == 0:
        return None
    return 100.0 * ctx.work.flops / (t * ctx.peaks["bf16_flops_per_s"])
