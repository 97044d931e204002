"""tpot_p90_ms: 90th percentile, over every request of the open loop, of
(retirement - first token) / (tokens - 1), on the benchmark's host clock
(first token: the end of its prefill-insert; retirement: the end of
``DecodeSession.retire``)."""

import numpy as np


def read(ctx):
    w = ctx.window
    if ctx.cell.traffic["loop"] != "open":
        return None
    first = {rid: t1 for rid, _, t1 in w.probe.admits}
    vals = [(t - first[rid]) * 1e3 / (n - 1)
            for rid, (t, n) in w.probe.retired.items() if n > 1]
    return float(np.percentile(vals, 90)) if vals else None
