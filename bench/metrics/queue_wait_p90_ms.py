"""queue_wait_p90_ms (scheduler, serving/server.py): 90th percentile of
the time from a request's due time to the start of its prefill-insert,
on the benchmark's host clock."""

import numpy as np


def read(ctx):
    w = ctx.window
    if not w.probe.admits:
        return None
    due = {r.request_id: w.t0 + r.arrival_s for r in ctx.requests}
    return float(np.percentile([(t0 - due[rid]) * 1e3
                                for rid, t0, _ in w.probe.admits], 90))
