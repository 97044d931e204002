"""ttft_p90_ms: 90th percentile, over every request of the open loop, of
the time from the request's due time to its first token: the end of its
prefill-insert (``DecodeSession.admit`` blocks until the token exists),
both on the benchmark's host clock."""

import numpy as np


def read(ctx):
    w = ctx.window
    if ctx.cell.traffic["loop"] != "open" or not w.probe.admits:
        return None
    due = {r.request_id: w.t0 + r.arrival_s for r in ctx.requests}
    return float(np.percentile([(t1 - due[rid]) * 1e3
                                for rid, _, t1 in w.probe.admits], 90))
