"""prefill_insert_ms (scheduler): median host span around
DecodeSession.admit, the serial batch-1 padded prefill-insert that ends
in block_until_ready."""

import numpy as np


def read(ctx):
    spans = [(t1 - t0) * 1e3 for _, t0, t1 in ctx.window.probe.admits]
    return float(np.median(spans)) if spans else None
