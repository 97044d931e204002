#!/usr/bin/env python3
"""Record the small device trace that ``test_trace.py`` reduces.

    python3 tests/bench/record_trace.py OUT.xplane.pb

Run on the chip: two tiny jitted programs (named ``step`` and ``insert``
like the program's own) run a few times inside a ``bench.window`` span,
with ``bench.run_chunk`` and ``bench.admit`` spans around them and host
sleeps between, under the JAX profiler. Prints each plane's lines with
their event counts, so the trace's layout can be read by eye.
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")

    def step(x):
        return jnp.tanh(x @ x) + 1.0

    def insert(x):
        return (x * 2.0).sum(axis=0)

    step_j, insert_j = jax.jit(step), jax.jit(insert)
    x = jnp.ones((512, 512), jnp.bfloat16)
    step_j(x).block_until_ready()
    insert_j(x).block_until_ready()
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.admit"):
                    insert_j(x).block_until_ready()
                time.sleep(0.002)
                with jax.profiler.TraceAnnotation("bench.run_chunk"):
                    y = x
                    for _ in range(4):
                        y = step_j(y)
                    y.block_until_ready()
                time.sleep(0.002)
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
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
