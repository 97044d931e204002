"""pass_device_ms (step programs, core/engine.py and
distributed/workers.py): device time of every program but the
prefill-insert, from the trace, per target pass."""


def read(ctx):
    t = ctx.decode_device_s()
    passes = sum(c.passes for c in ctx.window.probe.chunks)
    if not t or not passes:
        return None
    return t * 1e3 / passes
