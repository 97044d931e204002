#!/usr/bin/env python3
"""How many slots a configuration's chip holds, from ``memory_analysis()``.

Compiles the program's own decode step and prefill-insert at full width
for a described TPU v5e (no chip needed; keep ``JAX_PLATFORMS=cpu``) at
each slot count asked, with the context of each traffic mix asked, and
prints parameter, state and temporary bytes (or the compiler's refusal):

    JAX_PLATFORMS=cpu python3 bench/size_slots.py qwen3b-qwen05b \
        --traffic code batch --slots 32 24
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SECONDS = 51.0


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def measure(config: str, traffic_name: str, slots: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec as bspec
    from bench import traffic, weights
    from repro.core.engine import SpecDecodeEngine
    from repro.core.session import DecodeSession
    from repro.core.specdec import SpecDecodeState

    entry = {c["name"]: c for c in bspec.load_benchmark()["configs"]}[config]
    cfgfile = bspec.load_config(entry)
    target, draft, self_draft = bspec.config_models(cfgfile)
    sv = cfgfile["serving"]
    mp, mn = traffic.max_lengths(bspec.load_traffic(traffic_name), SECONDS)
    mp = -(-mp // sv["pad_to"]) * sv["pad_to"]

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def abstract(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    def shapes(m):
        return abstract(jax.eval_shape(bspec.family_of(m).program_params, m,
                                       weights.model_key(0, 0)))

    def program_config(m):
        return bspec.family_of(m).program_config(m)

    tp = shapes(target)
    dp = tp if self_draft else shapes(draft)
    eng = SpecDecodeEngine(program_config(draft), program_config(target),
                           draft_params=dp, target_params=tp,
                           temperature=0.0, gamma_max=sv["gamma_max"],
                           sync_every=sv["sync_every"])
    sess = DecodeSession(eng, capacity=slots, max_new_cap=mn,
                         max_prompt_len=mp, gamma_max=sv["gamma_max"],
                         sync_every=sv["sync_every"])
    L = sess.slots_len
    state = abstract(jax.eval_shape(lambda: SpecDecodeState(
        draft_cache=eng.draft.init_cache(slots, L),
        target_cache=eng.target.init_cache(slots, L),
        last_token=jnp.zeros((slots,), jnp.int32),
        pos=jnp.zeros((slots,), jnp.int32))))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    i32 = jnp.int32
    k = abstract(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    out_buf, cursor = sds((slots, mn), i32), sds((slots,), i32)
    nacc = sds((sv["sync_every"], slots), i32)
    step = eng._step_fn(sv["gamma_max"])
    c_step = step.lower(dp, tp, state, k, sds((), i32), sds((), i32),
                        out_buf, cursor, nacc, nacc, cursor,
                        sds((slots,), bool), sds((), i32)).compile()
    ins = eng._insert_step(slots, L, mp)
    c_ins = ins.lower(dp, tp, state, out_buf, cursor, cursor,
                      sds((slots,), bool), sds((1, mp), i32), sds((1,), i32),
                      sds((), i32), sds((), i32), k).compile()
    ms, mi = c_step.memory_analysis(), c_ins.memory_analysis()
    params = tree_bytes(tp) + (0 if self_draft else tree_bytes(dp))
    st = tree_bytes(state)
    row = {"config": config, "traffic": traffic_name, "slots": slots,
           "context": L, "max_prompt": mp, "max_new": mn,
           "param_bytes": params, "state_bytes": st,
           "step_temp_bytes": ms.temp_size_in_bytes,
           "insert_temp_bytes": mi.temp_size_in_bytes,
           "total_bytes": params + st + max(ms.temp_size_in_bytes,
                                            mi.temp_size_in_bytes)}
    if bspec.load_traffic(traffic_name).get("link"):
        # the transport path: the target's verify returns a new cache
        # beside the old one (its arguments are not donated)
        _, tw = eng.split_workers()
        c_ver = tw.verify_commit(sv["gamma_max"]).lower(
            tp, state.target_cache, sds((slots, sv["gamma_max"] + 1), i32),
            cursor, sds((), i32), k, out_buf, cursor, nacc, nacc, cursor,
            sds((slots,), bool), sds((), i32), sds((), i32)).compile()
        mv = c_ver.memory_analysis()
        row["verify_temp_bytes"] = mv.temp_size_in_bytes
        row["verify_output_bytes"] = mv.output_size_in_bytes
        row["total_bytes"] = max(row["total_bytes"],
                                 params + st + mv.output_size_in_bytes
                                 + mv.temp_size_in_bytes)
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--traffic", nargs="+", required=True)
    ap.add_argument("--slots", nargs="+", type=int, default=[32])
    args = ap.parse_args()
    import json
    for t in args.traffic:
        for n in args.slots:
            try:
                row = measure(args.config, t, n)
            except Exception as e:  # the chip's compiler refuses: too big
                row = {"config": args.config, "traffic": t, "slots": n,
                       "refused": str(e).splitlines()[0][:300]}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
