"""pass_roofline_share (kernels: the step's XLA fusions; no Pallas kernel
is on the dense path): the least time the needed work of the window's
passes could take on this chip, max(FLOPs / peak, bytes / bandwidth),
over the device time of the decode programs, in %."""


def read(ctx):
    t = ctx.decode_device_s()
    if not t or ctx.work.passes == 0:
        return None
    peak = ctx.peaks
    least = max(ctx.work.flops / peak["bf16_flops_per_s"],
                ctx.work.bytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / t
