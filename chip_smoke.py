#!/usr/bin/env python3
"""Smoke run of the served speculative-decoding path on a TPU.

    python3 chip_smoke.py                 # phases (a)-(d) on one chip
    python3 chip_smoke.py --four-chips    # process-backed pairs, 4 chips

One chip: the launcher's own code (``repro.launch.serve.serve`` over
``repro.topology.build_deployment``) serves 8 seeded requests
(``max_batch`` 4, ``max_new`` 64, AWC window policy) in each phase, with
every model at its published widths (``ClusterSpec.full_width``) and
random weights drawn from ``--seed``:

  (a) mamba2-130m drafts for qwen2.5-3b, colocated, dense KV;
  (b) the same pair over an emulated 20 ms link, ``mode_policy auto``, so
      the split draft and target workers run;
  (c) the same pair with a paged KV pool, so the paged Pallas decode
      kernel runs;
  (d) qwen2.5-3b drafts for itself (one shared parameter tree): the full
      151,936-row vocabulary and acceptance near 1.

Each phase then serves the same requests again with ``mode_policy
fused`` — target-only decoding through the same compiled programs — and
at temperature 0 every request's committed tokens must equal those.

``--four-chips`` runs only ``examples/cluster_2pair_procs.json`` at the
phase (a) pair: two process-backed pairs, four worker processes, each
bound to a chip of its own. This process never touches JAX. After the
workers exit, a child process serves the same topology's pairs
in-process on one chip, each on the requests the process-backed server
dealt it, and the committed tokens must match request for request.

Every line before the last is smoke output, not a measurement. The last
line is one JSON object: ``{"ok": true, "device": {...}}``. Without a TPU,
or when any phase fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.serve import serve  # noqa: E402
from repro.topology import (ClusterSpec, build_deployment,  # noqa: E402
                            node_key, one_pair_spec, resolve_node_configs)

PAIR_A = ("mamba2-130m", "qwen2.5-3b")       # (draft, target)
PAIR_D = ("qwen2.5-3b", "qwen2.5-3b")
FOUR_CHIP_TOPOLOGY = ROOT / "examples" / "cluster_2pair_procs.json"


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def pair_spec(pair: tuple[str, str], seed: int, full_width: bool,
              link_rtt_ms=None, paged_kv: bool = False) -> ClusterSpec:
    draft, target = pair
    spec = one_pair_spec(target=target, draft=draft, policy="awc",
                         max_batch=4, requests=8, max_new=64,
                         link_rtt_ms=link_rtt_ms, mode_policy="auto",
                         seed=seed)
    spec.full_width = full_width
    spec.serving.paged_kv = paged_kv
    return spec.validate()


def widths(cfg) -> str:
    if cfg.arch_type == "ssm":
        shape = (f"ssm_state={cfg.ssm_state} heads={cfg.ssm_heads}"
                 f"x{cfg.ssm_head_dim}")
    else:
        shape = (f"heads={cfg.n_heads}/{cfg.n_kv_heads} "
                 f"head_dim={cfg.head_dim} d_ff={cfg.d_ff}")
    return (f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
            f"{shape} dtype={cfg.dtype}")


def first_divergence(got, want) -> str:
    for i, (a, b) in enumerate(zip(got, want)):
        if a != b:
            return f"position {i}: {a} != fused {b}"
    return f"length {len(got)} != fused {len(want)}"


def check_tokens(label: str, got: dict, want: dict) -> None:
    if sorted(got) != sorted(want):
        raise AssertionError(f"{label}: served request ids {sorted(got)} "
                             f"!= {sorted(want)}")
    bad = [rid for rid in sorted(got) if got[rid] != want[rid]]
    for rid in bad:
        print(f"smoke: {label}: request {rid} diverges at "
              f"{first_divergence(got[rid], want[rid])}", file=sys.stderr)
    if bad:
        raise AssertionError(f"{label}: {len(bad)} request(s) committed "
                             f"tokens that differ from the reference")


def tokens_by_request(results) -> dict:
    return {r.request_id: [int(t) for t in r.tokens] for r in results}


def paged_step_has_kernel(dep) -> bool:
    """Compile the paged session's decode step again (a persistent-cache
    hit when the cache is on) and look for the Pallas custom call."""
    import jax.numpy as jnp
    pair = dep.pairs[0]
    eng, sess = pair.engine, pair.session
    step = eng._step_fn(sess.gamma_max)
    text = step.lower(
        eng.draft_params, eng.target_params, sess._state, sess._key,
        jnp.asarray(1, jnp.int32), jnp.asarray(0, jnp.int32), sess._out_buf,
        sess._cursor, sess._nacc, sess._nn, sess._max_new, sess._done,
        jnp.asarray(sess.eos_id, jnp.int32)).compile().as_text()
    return "tpu_custom_call" in text


def run_phase(label: str, spec: ClusterSpec, node_params: dict,
              published_vocab: int) -> None:
    import jax

    from repro.analysis.sanitize import persistent_cache_hits
    t0 = time.perf_counter()
    hits0 = persistent_cache_hits()
    dep = build_deployment(spec, node_params=node_params)
    for role, nid in (("draft", "edge0"), ("target", "cloud0")):
        say(f"{label}: {role} {widths(dep.node_configs[nid])}")
    say(f"{label}: vocabulary rows held {dep.vocab} of {published_vocab} "
        f"published ({dep.vocab / published_vocab:.3f})")
    results, summary = serve(spec, dep)
    for p in dep.pairs:
        p.mode_policy = "fused"
    reference, _ = serve(spec, dep)
    got, want = tokens_by_request(results), tokens_by_request(reference)
    check_tokens(label, got, want)
    if len(got) != spec.workload.num_requests:
        raise AssertionError(f"{label}: served {len(got)} of "
                             f"{spec.workload.num_requests} requests")
    pair = summary["pairs"]["pair0"]
    say(f"{label}: served {summary['requests']} requests, "
        f"{sum(len(t) for t in got.values())} tokens committed, "
        f"mean acceptance {summary['mean_acceptance']:.4f}, "
        f"mean gamma {pair['mean_gamma']}, fused fraction "
        f"{pair['fused_fraction']}; tokens equal fused mode for all "
        f"{len(got)} requests")
    if spec.serving.paged_kv:
        has_kernel = paged_step_has_kernel(dep)
        say(f"{label}: paged step HLO contains tpu_custom_call: "
            f"{has_kernel}")
        # interpret mode (the CPU rehearsal) inlines the kernel
        if jax.default_backend() == "tpu" and not has_kernel:
            raise AssertionError(f"{label}: the paged step compiled "
                                 f"without the Pallas kernel")
    stats = jax.devices()[0].memory_stats() or {}
    hits = persistent_cache_hits() - hits0
    say(f"{label}: compiled step programs "
        f"{summary['compiled_step_programs']}, device peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}, "
        f"compile cache hits {hits} ({'hit' if hits else 'miss'})")
    print(f"smoke: {label} wall {time.perf_counter() - t0:.1f} s "
          f"(includes compiles; not a measurement)", file=sys.stderr)


def one_chip(seed: int) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache {enable_compile_cache()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    published_vocab = get_config(PAIR_A[1]).vocab

    run_phases(seed, published_vocab)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def node_params(spec: ClusterSpec, share_target: bool = False) -> dict:
    """Each node's parameters, drawn as build_deployment would draw them;
    ``share_target`` hands the target's tree to every node."""
    from repro.models.model import build_model
    configs, _ = resolve_node_configs(spec)
    if share_target:
        p = build_model(configs["cloud0"]).init_params(
            node_key(spec, "cloud0"))
        return {nid: p for nid in configs}
    return {nid: build_model(c).init_params(node_key(spec, nid))
            for nid, c in configs.items()}


def run_phases(seed: int, published_vocab: int, full_width: bool = True,
               ) -> None:
    """Phases (a)-(d). ``full_width=False`` runs them on the reduced
    models, which is how the sequence is rehearsed on a CPU."""
    def spec(pair, **kw):
        return pair_spec(pair, seed, full_width, **kw)

    # (a)-(c) share one parameter set; it is freed before (d) draws its own
    spec_a = spec(PAIR_A)
    params = node_params(spec_a)
    run_phase("(a) colocated dense", spec_a, params, published_vocab)
    run_phase("(b) 20 ms link auto", spec(PAIR_A, link_rtt_ms=20.0), params,
              published_vocab)
    run_phase("(c) colocated paged", spec(PAIR_A, paged_kv=True), params,
              published_vocab)
    del params
    gc.collect()        # engines and their jitted steps form cycles
    spec_d = spec(PAIR_D)
    run_phase("(d) self-draft", spec_d, node_params(spec_d, share_target=True),
              published_vocab)


# -- four chips ---------------------------------------------------------------

def four_chip_spec(seed: int) -> ClusterSpec:
    spec = ClusterSpec.load(str(FOUR_CHIP_TOPOLOGY))
    draft, target = PAIR_A
    for n in spec.nodes:
        n.model = draft if n.role == "draft" else target
    spec.full_width = True
    spec.seed = seed
    return spec.validate()


def serve_in_process(spec_json: str) -> None:
    """Child of ``--four-chips``: the same topology's pairs served
    in-process on this process's first chip, one pair at a time (each
    pair's own nodes, so one target's parameters are resident at once),
    each on the requests the process-backed server dealt it (round robin
    in arrival order). Prints the committed tokens and the device as its
    last line."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import workload_requests
    enable_compile_cache()
    spec = ClusterSpec.from_json(spec_json)
    _, vocab = resolve_node_configs(spec)
    pending = sorted(workload_requests(spec, vocab),
                     key=lambda r: r.arrival_s)
    tokens = {}
    for i, pair in enumerate(spec.pairs):
        one = dataclasses.replace(
            spec, pairs=[dataclasses.replace(pair, process=False)])
        server = build_deployment(one).build_server()
        for req in pending[i::len(spec.pairs)]:
            server.submit(req)
        tokens.update(tokens_by_request(server.run()))
        del server
        gc.collect()
    dev = jax.devices()[0]
    print(json.dumps({"tokens": tokens,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


def four_chips(seed: int) -> dict:
    from jax._src import xla_bridge

    from repro.distributed.host import host_tpu_chips
    chips = host_tpu_chips()
    if chips < 4:
        raise SystemExit(f"chip_smoke --four-chips: needs a host with 4 TPU "
                         f"chips, found {chips}")
    spec = four_chip_spec(seed)
    for n in spec.nodes:
        say(f"four-chip: node {n.id} ({n.role}) {n.model}")
    dep = build_deployment(spec)        # spawns 4 workers, one chip each
    try:
        results, summary = serve(spec, dep)
    finally:
        dep.shutdown()
    if xla_bridge.backends_are_initialized():
        raise AssertionError("the parent process brought up a JAX backend")
    got = tokens_by_request(results)
    say(f"four-chip: process pairs served {summary['requests']} requests, "
        f"{sum(len(t) for t in got.values())} tokens committed, mean "
        f"acceptance {summary['mean_acceptance']:.4f}")
    child = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "chip_smoke.serve_in_process(sys.stdin.read())"],
        input=spec.to_json(), capture_output=True, text=True, cwd=ROOT,
        timeout=900)
    sys.stderr.write(child.stderr[-4000:])
    if child.returncode != 0:
        raise AssertionError(f"in-process reference exited "
                             f"{child.returncode}")
    ref = json.loads(child.stdout.strip().splitlines()[-1])
    want = {int(k): v for k, v in ref["tokens"].items()}
    check_tokens("four-chip", got, want)
    say(f"four-chip: tokens equal the in-process one-chip reference for "
        f"all {len(got)} requests")
    return ref["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the process-backed four-worker topology "
                         "and its one-chip in-process reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
