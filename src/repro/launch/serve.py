"""Serving launcher: edge-draft + cloud-target speculative decoding on real
JAX models with the paper's window policies, on the continuous slot-based
scheduler (default) or the wave-batched baseline.

TOPOLOGY-FIRST: the launcher's real input is a declarative
:class:`repro.topology.ClusterSpec` — nodes, draft→target pairs with
per-pair links/window/mode policies, serving knobs, workload:

    PYTHONPATH=src python -m repro.launch.serve \
        --topology examples/cluster_2pair.json [--requests 8] [--json]

The legacy flag surface still works and compiles down to an equivalent
ONE-PAIR spec through :func:`repro.topology.one_pair_spec` and the same
:func:`repro.topology.build_deployment` factory (old invocations stay
behaviorally identical):

    PYTHONPATH=src python -m repro.launch.serve \
        --target qwen3-14b --draft qwen2.5-3b --policy awc \
        --requests 16 --max-new 48 [--server continuous|wave] \
        [--arrival-rate 8] [--temperature 0.0] [--rtt-ms 10] \
        [--link-rtt-ms 20 --link-jitter-ms 2 --link-bw-gbps 1] \
        [--mode-policy auto|distributed|fused|pipeline]

``--arrival-rate`` draws Poisson arrivals (requests/s); TTFT and e2e are
measured from each request's arrival, so they include queue wait. Models
run as their reduced variants (2 layers, float32) by default, which is
what the CPU tests use; ``--full-width`` serves every node's model at its
published widths (``ClusterSpec.full_width``), which needs the chip.
Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR``, or in
``<repo>/.jax_cache`` when that is unset (:mod:`repro.launch.compile_cache`).

``--link-rtt-ms`` switches the continuous server to DISTRIBUTED execution:
speculation rounds run as real draft→verify→verdict exchanges over a
transport — zero-delay in-process at ``--link-rtt-ms 0`` (bit-identical to
the colocated path), an emulated edge-cloud link otherwise (measured
wall-clock delays; ``--link-jitter-ms``/``--link-bw-gbps`` shape it, and
the measured RTT feeds the AWC feature vector). ``--mode-policy`` forces
or frees the fused/distributed mode decision (``fused`` = cloud-only
autoregressive steps, no draft round trips).

Multi-pair topologies report link stats PER PAIR (``pairs`` in the JSON
summary, keyed by pair id); the one-pair case additionally keeps the old
flat ``link_*`` keys.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from ..configs import ARCHS
from ..serving import ServeRequest, WaveSpecDecodeServer
from ..topology import (ClusterSpec, Deployment, build_deployment,
                        one_pair_spec)
from .compile_cache import enable_compile_cache


def spec_from_args(args) -> ClusterSpec:
    """Compile the parsed CLI namespace to a ClusterSpec: ``--topology``
    loads the file (CLI workload flags override its workload section when
    explicitly passed); otherwise the legacy flags map to a one-pair
    spec."""
    if args.topology:
        spec = ClusterSpec.load(args.topology)
    else:
        spec = one_pair_spec(
            target=args.target, draft=args.draft, policy=args.policy,
            gamma=args.gamma, gamma_max=args.gamma_max,
            max_batch=args.max_batch, sync_every=args.sync_every,
            temperature=args.temperature, rtt_ms=args.rtt_ms,
            link_rtt_ms=args.link_rtt_ms,
            link_jitter_ms=args.link_jitter_ms,
            link_bw_gbps=args.link_bw_gbps, mode_policy=args.mode_policy,
            server=args.server, seed=args.seed)
    if args.requests is not None:
        spec.workload.num_requests = args.requests
    if args.max_new is not None:
        spec.workload.max_new = args.max_new
    if args.arrival_rate is not None:
        spec.workload.rate_per_s = args.arrival_rate
    if args.full_width:
        spec.full_width = True
    return spec.validate()


def workload_requests(spec: ClusterSpec, vocab: int) -> list:
    """The spec's seeded request stream: the fleet trace's class-aware
    arrivals with per-class SLOs when the workload declares one (the SAME
    stream build_simulation replays), else ``num_requests`` uniform prompts
    with Poisson arrivals at ``rate_per_s`` (all at t=0 when 0)."""
    wl = spec.workload
    if wl.trace is not None:
        from ..fleet.workload import fleet_serve_requests, generate_requests
        return list(fleet_serve_requests(generate_requests(wl.trace), vocab,
                                         seed=spec.seed))
    rng = np.random.default_rng(spec.seed)
    arrival = 0.0
    reqs = []
    for i in range(wl.num_requests):
        plen = int(rng.integers(wl.prompt_lo, wl.prompt_hi))
        if wl.rate_per_s > 0:
            arrival += float(rng.exponential(1.0 / wl.rate_per_s))
        reqs.append(ServeRequest(
            i, rng.integers(0, vocab, plen).astype(np.int32), wl.max_new,
            arrival_s=arrival))
    return reqs


def serve(spec: ClusterSpec, deployment: Deployment,
          topology: str = "") -> tuple[list, dict]:
    """Serve the spec's workload through ``deployment``: build the server
    (continuous, or the wave baseline), submit :func:`workload_requests`,
    drain it. Returns the per-request results and the summary
    :func:`main` prints. Leaves the deployment running; the caller owns
    :meth:`~repro.topology.Deployment.shutdown`."""
    if spec.serving.server == "wave":
        pair0 = deployment.pairs[0]
        cfg = deployment.server_config()
        # the wave baseline reads mode_policy off its ServerConfig (it has
        # no pair objects); forward the single pair's declared mode
        cfg.mode_policy = pair0.mode_policy
        server = WaveSpecDecodeServer(pair0.engine, pair0.policy, cfg)
    else:
        server = deployment.build_server()
    for req in workload_requests(spec, deployment.vocab):
        server.submit(req)
    results = server.run()

    accs = [r.acceptance_rate for r in results]
    tpots = [r.tpot_ms for r in results]
    summary = {
        "server": spec.serving.server,
        "topology": topology or "one-pair(flags)",
        "pairs_deployed": len(deployment.pairs),
        "requests": len(results),
        "mean_acceptance": float(np.mean(accs)),
        "mean_ttft_ms": float(np.mean([r.ttft_ms for r in results])),
        "mean_queue_ms": float(np.mean([r.queue_ms for r in results])),
        "mean_tpot_ms": float(np.mean(tpots)),
        "mean_e2e_ms": float(np.mean([r.e2e_ms for r in results])),
        "compiled_step_programs": sum(
            p.engine.compiled_programs()
            for p in {id(p.engine): p for p in deployment.pairs
                      if p.engine is not None}.values()),
    }
    if spec.workload.trace is not None:
        from ..fleet.workload import serve_results_rows, slo_report
        summary["slo"] = slo_report(serve_results_rows(results))
    if hasattr(server, "pair_summaries"):
        summary["pairs"] = server.pair_summaries()
    # one-pair backcompat: the flat link keys the pre-topology launcher
    # emitted, read off the single pair's transport
    if len(deployment.pairs) == 1:
        tr = deployment.pairs[0].transport
        if tr is not None:
            summary["transport"] = tr.describe()
            summary["mode_policy"] = deployment.pairs[0].mode_policy
            summary["link_bytes_sent"] = tr.bytes_sent
            summary["link_messages"] = tr.messages_sent
            summary["link_recent_rtt_ms"] = round(tr.recent_rtt_ms, 3)
    return results, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default=None, metavar="cluster.json",
                    help="declarative ClusterSpec (nodes + draft→target "
                         "pairs with per-pair links/policies); replaces "
                         "the one-pair flag surface below")
    ap.add_argument("--target", default="qwen3-14b", choices=sorted(ARCHS))
    ap.add_argument("--draft", default="qwen2.5-3b", choices=sorted(ARCHS))
    ap.add_argument("--policy", default="static",
                    choices=["static", "dynamic", "awc"])
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None,
                    help="request count (default: topology workload, or 8)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="tokens per request (default: topology workload, "
                         "or 32)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--server", default="continuous",
                    choices=["continuous", "wave"],
                    help="continuous slot scheduler vs wave-batched baseline")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrivals per second (0 = all at t=0)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--rtt-ms", type=float, default=10.0,
                    help="virtual RTT charged by the colocated path "
                         "(ignored when --link-rtt-ms selects a transport)")
    ap.add_argument("--link-rtt-ms", type=float, default=None,
                    help="run distributed over a transport: 0 = in-process "
                         "(zero delay), >0 = emulated edge-cloud link with "
                         "this RTT (measured wall-clock delays)")
    ap.add_argument("--link-jitter-ms", type=float, default=1.0,
                    help="emulated link jitter (with --link-rtt-ms > 0)")
    ap.add_argument("--link-bw-gbps", type=float, default=1.0,
                    help="emulated link bandwidth (with --link-rtt-ms > 0)")
    ap.add_argument("--mode-policy", default="auto",
                    choices=["auto", "distributed", "fused", "pipeline"],
                    help="honor the window policy's fused/distributed "
                         "decision (auto) or force one mode; 'pipeline' "
                         "honors the decision AND overlaps window k+1's "
                         "draft with window k's verification (needs "
                         "--link-rtt-ms; pays off when RTT is at least "
                         "the target step time)")
    ap.add_argument("--gamma-max", type=int, default=12,
                    help="compile-once window bound; any policy γ ≤ this "
                         "runs without recompiling")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode iterations between host stat syncs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-width", action="store_true",
                    help="serve every node's model at its published widths "
                         "instead of the reduced CPU variant")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.link_rtt_ms is not None and args.server == "wave":
        raise SystemExit("--link-rtt-ms needs the continuous server "
                         "(the wave baseline is colocated-only)")
    if args.mode_policy == "pipeline" and args.link_rtt_ms is None \
            and not args.topology:
        raise SystemExit("--mode-policy pipeline overlaps rounds across a "
                         "transport; pass --link-rtt-ms (0 = in-process)")

    spec = spec_from_args(args)
    enable_compile_cache()
    deployment = build_deployment(spec)
    try:
        results, summary = serve(spec, deployment, args.topology or "")
    finally:
        deployment.shutdown()
    if not args.topology:
        summary["policy"] = args.policy
    if args.json:
        print(json.dumps(summary, indent=1))
    else:
        per_pair = ""
        if summary["pairs_deployed"] > 1 and "pairs" in summary:
            per_pair = "  " + "  ".join(
                (f"[{pid}: γ={d['mean_gamma']:.2f} "
                 f"fused={d['fused_fraction']:.2f} n={d['requests']}]")
                if "mean_gamma" in d else
                (f"[{pid}: process acc={d.get('acceptance_rate', 0):.2f} "
                 f"n={d['requests']}]")
                for pid, d in summary["pairs"].items())
        slo_txt = ""
        if "slo" in summary and summary["slo"]["graded"]:
            slo_txt = f"  slo={summary['slo']['attainment']:.2f}"
        print(f"served {summary['requests']} requests  "
              f"server={summary['server']}  "
              f"pairs={summary['pairs_deployed']}  "
              f"acceptance={summary['mean_acceptance']:.3f}  "
              f"ttft={summary['mean_ttft_ms']:.1f}ms  "
              f"tpot={summary['mean_tpot_ms']:.1f}ms  "
              f"e2e={summary['mean_e2e_ms']:.0f}ms  "
              f"programs={summary['compiled_step_programs']}"
              + slo_txt + per_pair)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
