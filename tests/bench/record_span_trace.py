#!/usr/bin/env python3
"""Record the small device trace with program spans that
``test_spans.py`` reduces.

    python3 tests/bench/record_span_trace.py OUT.xplane.pb

Run on the chip: two tiny jitted programs (named ``step`` and ``insert``
like the program's own) run inside a ``bench.window`` span, with the
program's ``dsd.*`` spans (``repro.tracing.span``, metadata included)
nested in the benchmark's ``bench.admit`` and ``bench.run_chunk`` spans
as a serve nests them: three admissions, each followed by a chunk of
four fused rounds whose control round trip sleeps 2 ms, then a 2 ms
wait for the next arrival. Prints each host span with its metadata and
each plane's lines with their event counts, so the trace's layout can be
read by eye.
"""

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.tracing import span  # noqa: E402

SLEEP_S = 0.002


def serve(step_j, insert_j, x) -> None:
    bench = jax.profiler.TraceAnnotation
    with bench("bench.window"), span("serve", requests=3):
        for k in range(3):
            with bench("bench.admit"), span("admit", request_id=k,
                                            arrival_s=0.25 * k,
                                            prompt_len=512):
                with span("admit.dispatch"):
                    y = insert_j(x)
                with span("admit.wait"):
                    y.block_until_ready()
            with bench("bench.run_chunk"), span("chunk", active=k + 1):
                y = x
                for _ in range(4):
                    with bench("bench.decide"), span("decide"):
                        pass
                    with span("round", gamma=0, fused=True):
                        with span("verify"):
                            y = step_j(y)
                        with span("verdict.sync"):
                            np.asarray(y[0, 0])
                        with span("link.control"):
                            time.sleep(SLEEP_S)
                with span("chunk.sync"):
                    np.asarray(y[0, 0])
                with span("chunk.commit", tokens=4 * (k + 1),
                          slot_rounds=4 * (k + 1), fused=4):
                    pass
            with span("serve.idle"):
                time.sleep(SLEEP_S)


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_span_trace: needs a TPU")

    def step(x):
        return jnp.tanh(x @ x) + 1.0

    def insert(x):
        return (x * 2.0).sum(axis=0)

    step_j, insert_j = jax.jit(step), jax.jit(insert)
    x = jnp.ones((512, 512), jnp.bfloat16)
    serve(step_j, insert_j, x)          # compile everything first
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        serve(step_j, insert_j, x)
    path = sorted(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(path, out)
    shutil.rmtree(d)
    pd = jax.profiler.ProfileData.from_file(out)
    for p in pd.planes:
        print("plane", repr(p.name))
        for ln in p.lines:
            evs = list(ln.events)
            print("   line", repr(ln.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
            for e in evs:
                if e.name.startswith(("dsd.", "bench.")):
                    print("      span", repr(e.name), e.start_ns,
                          e.duration_ns, list(e.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
