"""tokens_per_s: output tokens committed in the window (first tokens of
the requests admitted in it, and every token a decode chunk committed,
requests still running at its end included) over the window, from the
serve loop's start to the chunk boundary that closed it."""


def read(ctx):
    w = ctx.window
    if w.t1 <= w.t0:
        return None
    tokens = sum(c.tokens for c in w.probe.chunks) + len(w.probe.admits)
    return tokens / (w.t1 - w.t0)
