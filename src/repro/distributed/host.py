"""Worker-host entrypoint: one draft or target process per node.

``python -m repro.distributed.host --role {draft,target} --topology
cluster.json --pair pair0 ...`` runs ONE side of a draft–target pair in
its own OS process with its own jax device context — the paper's Fig. 1b
deployment with an actual process boundary instead of the in-process
emulation. The two sides connect over the two TCP streams of a
:class:`repro.distributed.SocketTransport` (windows one way, verdicts the
other, control frames on both) and exchange exactly the bytes
:mod:`repro.distributed.wire` frames.

Determinism across the boundary: both hosts rebuild their model
parameters from the topology's seed with the SAME PRNG scheme
:func:`repro.topology.build_deployment` uses (``kd, kt = split(
PRNGKey(spec.seed))``, i-th node of a role folds in ``i``), so no
parameter shipping is needed; overridden tiny configs/params travel as
JSON/npz files written by :func:`spawn_pair`. Each wave both hosts admit
the SAME prompts into a persistent session through the engine's jitted
per-slot prefill-insert program (duplicated prefill — the admission cost
of not shipping KV; only decode-round bytes cross the wire, as in the
paper), and the target replies with the per-slot anchor tokens so drift
is caught at admission, not as a token mismatch downstream. Reusing one
session per wave geometry keeps admission on the compiled path: the
first wave pays every jit compile once, steady-state waves cost one
batch-1 insert per slot plus the decode rounds. Greedy decoding ignores PRNG keys entirely, which is why
process pairs are restricted to ``temperature == 0``.

Per decode round the draft host proposes ``γ_max`` tokens and ships a
:class:`~repro.distributed.wire.WindowMsg`; the target host verifies and
commits on ITS session (the ground-truth output buffers live target-side,
as they would in a real cloud) and ships the
:class:`~repro.distributed.wire.VerdictMsg` back; the draft reconstructs
its state from the verdict alone (``pos += num_new``, anchor =
``last_token``, attention drafts keep the propose cache, recurrent drafts
re-advance) — the same reconstruction rule
``DecodeSession._run_chunk_transport`` applies in process.

Steady-state waves (after the first, which absorbs jit compilation) run
under the :func:`repro.analysis.sanitize.compile_guard` sentry on both
hosts: a recompile mid-measurement crashes the host with a nonzero exit
instead of silently poisoning throughput numbers.

The parent side (:func:`spawn_pair` → :class:`PairHostHandle`) is what
``repro.topology.build_deployment`` uses for ``process: true`` pairs: it
launches the two hosts, performs the port handshake over their stdout,
and drives waves over a framed control connection to the draft host.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import select
import socket
import subprocess
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Optional

import numpy as np

from .socket_transport import (FRAME_CONTROL, SocketTransport, recv_frame,
                               send_frame)
from .transport import CONTROL_PAYLOAD_BYTES
from .wire import TransportProtocolError, VerdictMsg, WindowMsg

_HELLO = {b"W": "window", b"V": "verdict"}
_READY_TIMEOUT_S = 300.0     # engine build + warmup on a cold jit cache


# --------------------------------------------------------------------------
# config / param shipping (overrides only; defaults rebuild from the seed)
# --------------------------------------------------------------------------

def save_model_config(cfg, path: str) -> None:
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(cfg), f)


def load_model_config(path: str):
    from ..configs.base import ModelConfig
    with open(path) as f:
        return ModelConfig(**json.load(f))


def save_params(params, path: str) -> None:
    """Flatten a param tree to an npz in traversal order. The structure
    is NOT stored: :func:`load_params` rebuilds the template tree from
    the node's config, so order-stable flattening is enough."""
    import jax
    leaves = jax.tree.leaves(params)
    np.savez(path, **{f"leaf_{i}": np.asarray(a)
                      for i, a in enumerate(leaves)})


def load_params(cfg, path: str):
    import jax

    from ..models.model import build_model
    template = jax.eval_shape(build_model(cfg).init_params,
                              jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(template)
    with np.load(path) as z:
        loaded = [z[f"leaf_{i}"] for i in range(len(leaves))]
    if len(loaded) != len(leaves):  # pragma: no cover - config drift
        raise ValueError(f"param file {path} has {len(loaded)} leaves, "
                         f"config expects {len(leaves)}")
    return jax.tree.unflatten(treedef, [
        np.asarray(a, dtype=t.dtype) for a, t in zip(loaded, leaves)])


# --------------------------------------------------------------------------
# shared host plumbing
# --------------------------------------------------------------------------

def _parse_kv(entries) -> dict:
    out = {}
    for e in entries or []:
        k, _, v = e.partition("=")
        if not k or not v:
            raise SystemExit(f"expected NAME=PATH, got {e!r}")
        out[k] = v
    return out


class _HostContext:
    """Everything one host process shares across waves: the resolved
    spec/pair, the engine (params rebuilt from the seed scheme), and the
    socket endpoint."""

    def __init__(self, args):
        from ..topology import ClusterSpec, TopologyError
        self.args = args
        self.spec = ClusterSpec.load(args.topology).validate()
        for p in self.spec.pairs:
            if p.id == args.pair:
                self.pair = p
                break
        else:
            raise TopologyError(f"unknown pair id {args.pair!r}")
        validate_process_pair(self.spec, self.pair)
        if self.spec.full_width:
            # the workers of a deployment compile the same full-width
            # programs: share them, and keep them for the next run
            from ..launch.compile_cache import enable_compile_cache
            enable_compile_cache()
        self.model_configs = {}
        for name, path in _parse_kv(args.model_config).items():
            self.model_configs[name] = load_model_config(path)
        self.node_param_paths = _parse_kv(args.node_params)
        self.role = args.role
        self.node = self.spec.node(self.pair.draft if self.role == "draft"
                                   else self.pair.target)
        self.engine = None
        self.wave_index = 0
        self.sess = None
        self._sess_geom = None

    # -- engine (same construction rule as build_deployment) ---------------

    def build_engine(self):
        import jax

        from ..core.engine import SpecDecodeEngine
        from ..models.model import build_model
        from ..topology import node_key, resolve_node_configs
        spec, s = self.spec, self.spec.serving
        configs, _vocab = resolve_node_configs(spec, self.model_configs)
        params = {}
        for nid in (self.pair.draft, self.pair.target):
            if nid in self.node_param_paths:
                params[nid] = load_params(configs[nid],
                                          self.node_param_paths[nid])
            else:
                params[nid] = build_model(configs[nid]).init_params(
                    node_key(spec, nid))

        self.engine = SpecDecodeEngine(
            configs[self.pair.draft], configs[self.pair.target],
            draft_params=params[self.pair.draft],
            target_params=params[self.pair.target],
            temperature=s.temperature, rtt_ms=s.rtt_ms,
            gamma_max=s.gamma_max, sync_every=s.sync_every,
            key=jax.random.PRNGKey(spec.seed))
        return self.engine

    def wave_session(self, capacity: int, max_new_cap: int, pad_len: int):
        """ONE persistent session per wave geometry. Waves admit into
        retired slots through the engine's jitted prefill-insert program,
        so steady-state admission costs one compiled batch-1 insert per
        slot — ``admit_batch``'s eager batched prefill re-traces its
        layer scans every call (seconds per wave on a small host). A
        geometry change rebuilds the session and resets the recompile
        guard to a cold wave (new programs legitimately compile)."""
        from ..core.session import DecodeSession
        geom = (capacity, max_new_cap, pad_len)
        if self.sess is not None and self._sess_geom == geom:
            return self.sess
        s = self.spec.serving
        self.sess = DecodeSession(self.engine, capacity=capacity,
                                  max_new_cap=max_new_cap,
                                  max_prompt_len=pad_len,
                                  gamma_max=s.gamma_max,
                                  sync_every=s.sync_every,
                                  eos_id=s.eos_id, log_gamma=False,
                                  mode_policy="distributed")
        self._sess_geom = geom
        self.wave_index = 0
        return self.sess

    def guard(self):
        """Recompile sentry for steady-state waves; the first wave absorbs
        every jit compile (prefill, propose, verify) unguarded."""
        if self.wave_index == 0:
            return nullcontext()
        from ..analysis.sanitize import compile_guard
        return compile_guard(
            allowed=0,
            what=f"{self.role} host steady-state wave {self.wave_index}")


def validate_process_pair(spec, pair) -> None:
    """The restrictions a pair must satisfy before a process boundary can
    split it (raises :class:`repro.topology.TopologyError`)."""
    from ..topology import TopologyError
    if spec.serving.temperature > 0.0:
        raise TopologyError(
            f"pair {pair.id!r}: process-backed pairs are greedy-only "
            "(temperature 0) — q_probs never crosses the byte seam")
    if pair.mode_policy != "distributed":
        raise TopologyError(
            f"pair {pair.id!r}: process-backed pairs need "
            f"mode_policy='distributed' (got {pair.mode_policy!r}); "
            "fused flushes and pipelined rollback are not split yet")
    if pair.window.kind != "static":
        raise TopologyError(
            f"pair {pair.id!r}: process-backed pairs need a static window "
            f"policy (got {pair.window.kind!r}); feature-driven policies "
            "would need feature mirroring across the boundary")


def _admit_wave(sess, prompts, lens, max_new, request_ids) -> None:
    """Admit one wave per slot via the jitted prefill-insert. Free slots
    are taken in ascending index order, so slot i holds request i on both
    hosts — the anchor-divergence check below compares row for row."""
    ids = request_ids if request_ids is not None else list(range(len(lens)))
    for i in range(prompts.shape[0]):
        sess.admit(prompts[i, :int(lens[i])], int(max_new[i]),
                   request_id=int(ids[i]))


def _retire_wave(sess) -> None:
    """Free every slot after a wave's tokens have been shipped, so the
    next wave re-admits into the same live session."""
    for j in list(sess.occupied):
        sess.retire(j)


def _log(role: str, msg: str) -> None:
    print(f"{msg}", flush=True)
    print(f"[{role}-host] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# target host
# --------------------------------------------------------------------------

def run_target(args) -> int:
    """Accept the two streams, build the engine, then serve verify/commit
    rounds and control commands until ``shutdown``."""
    import jax

    from ..core.specdec import SpecDecodeState

    ctx = _HostContext(args)
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    port = args.listen_port if args.listen_port else (ctx.node.port or 0)
    lst.bind((args.bind_host, port))
    lst.listen(2)
    _log("target", f"listening port={lst.getsockname()[1]}")
    lst.settimeout(args.timeout_s)
    streams = {}
    for _ in range(2):
        conn, _addr = lst.accept()
        conn.settimeout(args.timeout_s)
        hello = conn.recv(1)
        tag = _HELLO.get(hello)
        if tag is None or tag in streams:
            raise TransportProtocolError(f"bad stream hello {hello!r}")
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        streams[tag] = conn
    lst.close()
    link = ctx.pair.link if (ctx.pair.link and ctx.pair.link.rtt_ms > 0) \
        else None
    ep = SocketTransport.target_endpoint(
        streams["window"], streams["verdict"], link=link,
        seed=ctx.spec.seed, timeout_s=args.timeout_s)

    ctx.build_engine()
    _, tw = ctx.engine.split_workers()
    _log("target", "ready")

    kv_key = None
    sess = None
    r_in_chunk = 0
    chunk_gammas: list[int] = []
    chunk_t0 = time.perf_counter()

    def flush_attribution():
        nonlocal r_in_chunk, chunk_gammas, chunk_t0
        if sess is not None and r_in_chunk:
            sess._sync_and_attribute(r_in_chunk, chunk_gammas, chunk_t0,
                                     non_target_ms=0.0)
        r_in_chunk = 0
        chunk_gammas = []
        chunk_t0 = time.perf_counter()

    while True:
        item, _w = ep.recv_window()
        if isinstance(item, dict):
            cmd = item.get("cmd")
            if cmd == "admit":
                prompts = np.asarray(item["prompts"], np.int32)
                lens = np.asarray(item["prompt_lens"], np.int32)
                max_new = np.asarray(item["max_new"], np.int32)
                sess = ctx.wave_session(prompts.shape[0],
                                        int(item["max_new_cap"]),
                                        prompts.shape[1])
                _admit_wave(sess, prompts, lens, max_new,
                            item.get("request_ids"))
                r_in_chunk, chunk_gammas = 0, []
                chunk_t0 = time.perf_counter()
                anchors = np.asarray(sess._state.last_token)
                ep._post("verdict", {"cmd": "admitted",
                                     "last_token": anchors.tolist()},
                         CONTROL_PAYLOAD_BYTES)
            elif cmd == "fetch":
                flush_attribution()
                tokens, stats = sess.snapshot()
                ep._post("verdict", {
                    "cmd": "tokens",
                    "tokens": tokens.tolist(),
                    "produced": np.asarray(stats.produced).tolist(),
                    "acceptance_seqs": [list(map(int, b))
                                        for b in stats.acceptance_seqs],
                    "stats": {"iterations": sess.iterations,
                              "proposed": sess.proposed,
                              "accepted": sess.accepted,
                              "prefill_s": sess.prefill_s},
                }, CONTROL_PAYLOAD_BYTES)
                ctx.wave_index += 1
                _retire_wave(sess)
            elif cmd == "shutdown":
                ep._post("verdict", {"cmd": "bye"}, CONTROL_PAYLOAD_BYTES)
                ep.close()
                return 0
            else:
                raise TransportProtocolError(f"unknown control {item!r}")
            continue

        msg: WindowMsg = item
        state = sess._state
        window_np = np.concatenate(
            [np.asarray(state.last_token)[:, None], msg.tokens], axis=1)
        if kv_key is None:
            kv_key = jax.random.PRNGKey(0)   # greedy: never read
        with ctx.guard():
            (tcache, new_pos, new_last, num_new_dev, nacc_dev,
             next_raw) = sess._verify_commit_round(
                tw, window_np, msg.gamma, r_in_chunk, None, False, kv_key)
            done_host = np.asarray(sess._done)
        verdict = VerdictMsg(
            n_accepted=np.asarray(nacc_dev), num_new=np.asarray(num_new_dev),
            next_token=np.asarray(next_raw), last_token=np.asarray(new_last),
            done=done_host, gamma=msg.gamma, n_active=msg.n_active,
            round_id=msg.round_id)
        ep.post_verdict(verdict)
        sess._state = SpecDecodeState(
            draft_cache=state.draft_cache, target_cache=tcache,
            last_token=new_last, pos=new_pos)
        chunk_gammas.append(msg.gamma)
        sess.iterations += 1
        r_in_chunk += 1
        if r_in_chunk >= sess.sync_every:
            flush_attribution()


# --------------------------------------------------------------------------
# draft host
# --------------------------------------------------------------------------

def run_draft(args) -> int:
    """Connect the two streams to the target host, build the engine, then
    serve framed control commands (``run``/``stats``/``shutdown``) from
    the parent over a local TCP control port."""
    import jax
    import jax.numpy as jnp

    from ..core.specdec import SpecDecodeState

    ctx = _HostContext(args)
    # control listener FIRST so the parent can read the port while the
    # target is still building (the connect below may wait on its accept)
    ctrl_lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    ctrl_lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    ctrl_lst.bind((args.bind_host, args.listen_port or 0))
    ctrl_lst.listen(1)
    _log("draft", f"listening port={ctrl_lst.getsockname()[1]}")
    ctrl_lst.settimeout(_READY_TIMEOUT_S)

    if args.connect:
        host, _, port_s = args.connect.rpartition(":")
        t_addr = (host or "127.0.0.1", int(port_s))
    else:
        t_node = ctx.spec.node(ctx.pair.target)
        t_addr = (t_node.address or "127.0.0.1", t_node.port)
    socks = {}
    for hello in (b"W", b"V"):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.settimeout(args.timeout_s)
        s.connect(t_addr)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.sendall(hello)
        socks[hello] = s
    link = ctx.pair.link if (ctx.pair.link and ctx.pair.link.rtt_ms > 0) \
        else None
    ep = SocketTransport.draft_endpoint(
        socks[b"W"], socks[b"V"], link=link, seed=ctx.spec.seed,
        timeout_s=args.timeout_s)

    ctx.build_engine()
    dw, _ = ctx.engine.split_workers()
    gamma = min(ctx.pair.window.gamma, ctx.engine.gamma_max)
    _log("draft", "ready")

    ctrl, _addr = ctrl_lst.accept()
    ctrl.settimeout(args.timeout_s)
    ctrl_lst.close()
    round_seq = 0

    def run_wave(cmd: dict) -> dict:
        nonlocal round_seq
        prompts = np.asarray(cmd["prompts"], np.int32)
        lens = np.asarray(cmd["prompt_lens"], np.int32)
        max_new = np.asarray(cmd["max_new"], np.int32)
        max_new_cap = int(cmd["max_new_cap"])
        B = prompts.shape[0]
        G = ctx.engine.gamma_max

        sess = ctx.wave_session(B, max_new_cap, prompts.shape[1])
        t_admit0 = time.perf_counter()
        _admit_wave(sess, prompts, lens, max_new, cmd.get("request_ids"))
        prefill_s = time.perf_counter() - t_admit0
        ep._post("window", {"cmd": "admit", "prompts": prompts.tolist(),
                            "prompt_lens": lens.tolist(),
                            "max_new": max_new.tolist(),
                            "max_new_cap": max_new_cap,
                            "request_ids": cmd.get("request_ids")},
                 CONTROL_PAYLOAD_BYTES)
        reply, _ = ep.recv_verdict()
        if not (isinstance(reply, dict) and reply.get("cmd") == "admitted"):
            raise TransportProtocolError(f"expected admitted, got {reply!r}")
        anchors_local = np.asarray(sess._state.last_token)
        anchors_remote = np.asarray(reply["last_token"], np.int32)
        if not np.array_equal(anchors_local, anchors_remote):
            raise TransportProtocolError(
                f"prefill anchors diverged across the process boundary: "
                f"draft {anchors_local.tolist()} vs target "
                f"{anchors_remote.tolist()} — params/config drift")

        state = sess._state
        done = np.zeros(B, bool)
        rounds, cap = 0, 2 * max_new_cap + 4
        key = jax.random.PRNGKey(0)                  # greedy: never read
        t_decode0 = time.perf_counter()
        while not done.all() and rounds < cap:
            with ctx.guard():
                toks, _q, dcache_prop = dw.propose(G)(
                    dw.params, state.draft_cache, state.last_token,
                    state.pos, key)
                toks_np = np.asarray(toks)
            msg = WindowMsg(tokens=toks_np, gamma=gamma,
                            n_active=int(B - done.sum()),
                            round_id=round_seq)
            round_seq += 1
            ep.post_window(msg)
            verdict, _w = ep.recv_verdict()
            num_new = jnp.asarray(verdict.num_new)
            new_last = jnp.asarray(verdict.last_token)
            with ctx.guard():
                if dw.attention:
                    dcache = dcache_prop   # pos_map masks the stale tail
                else:
                    window_np = np.concatenate(
                        [np.asarray(state.last_token)[:, None], toks_np],
                        axis=1)
                    dcache = dw.advance(G)(dw.params, state.draft_cache,
                                           jnp.asarray(window_np),
                                           state.pos, num_new)
            state = SpecDecodeState(
                draft_cache=dcache, target_cache=state.target_cache,
                last_token=new_last, pos=state.pos + num_new)
            done = np.asarray(verdict.done)
            rounds += 1
        decode_s = time.perf_counter() - t_decode0

        ep._post("window", {"cmd": "fetch"}, CONTROL_PAYLOAD_BYTES)
        result, _ = ep.recv_verdict()
        if not (isinstance(result, dict) and result.get("cmd") == "tokens"):
            raise TransportProtocolError(f"expected tokens, got {result!r}")
        ctx.wave_index += 1
        _retire_wave(sess)
        result.update(cmd="result", rounds=rounds,
                      prefill_s=prefill_s, decode_s=decode_s,
                      link_stats=transport_stats(ep))
        return result

    while True:
        kind, payload, _r, _d = recv_frame(ctrl)
        if kind != FRAME_CONTROL:
            raise TransportProtocolError(
                f"parent control channel got frame kind {kind}")
        cmd = json.loads(payload.decode("utf-8"))
        op = cmd.get("cmd")
        if op == "run":
            out = run_wave(cmd)
        elif op == "stats":
            out = {"cmd": "stats", "link_stats": transport_stats(ep),
                   "waves": ctx.wave_index}
        elif op == "shutdown":
            ep._post("window", {"cmd": "shutdown"}, CONTROL_PAYLOAD_BYTES)
            bye, _ = ep.recv_verdict()
            ep.close()
            send_frame(ctrl, FRAME_CONTROL,
                       json.dumps({"cmd": "bye"}).encode("utf-8"))
            ctrl.close()
            return 0
        else:
            raise TransportProtocolError(f"unknown parent command {cmd!r}")
        send_frame(ctrl, FRAME_CONTROL, json.dumps(out).encode("utf-8"))


def transport_stats(tr: SocketTransport) -> dict:
    return {"bytes_sent": tr.bytes_sent, "wire_bytes": tr.wire_bytes,
            "messages_sent": tr.messages_sent,
            "recent_rtt_ms": tr.recent_rtt_ms,
            "transport": tr.describe()}


# --------------------------------------------------------------------------
# parent side: spawn + drive a process-backed pair
# --------------------------------------------------------------------------

def _read_line(proc: subprocess.Popen, match: str, timeout_s: float,
               who: str) -> str:
    """Read stdout lines until one starts with ``match`` (deadline-bound,
    non-blocking so a wedged child cannot hang the parent forever)."""
    fd = proc.stdout.fileno()
    os.set_blocking(fd, False)
    deadline = time.monotonic() + timeout_s
    buf = b""
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{who} exited with code {proc.returncode} before "
                f"printing {match!r}")
        r, _, _ = select.select([fd], [], [], 0.25)
        if not r:
            continue
        chunk = os.read(fd, 4096)
        if not chunk:
            continue
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            text = line.decode("utf-8", "replace").strip()
            if text.startswith(match):
                return text
    raise TimeoutError(f"{who} did not print {match!r} in {timeout_s:.0f}s")


def _ctrl_call(sock: socket.socket, cmd: dict) -> dict:
    send_frame(sock, FRAME_CONTROL, json.dumps(cmd).encode("utf-8"))
    kind, payload, _r, _d = recv_frame(sock)
    if kind != FRAME_CONTROL:
        raise TransportProtocolError(f"control reply had frame kind {kind}")
    return json.loads(payload.decode("utf-8"))


@dataclass
class PairHostHandle:
    """Parent-side handle to one process-backed pair: the two host
    processes plus the framed control connection to the draft host."""
    pair_id: str
    procs: list
    ctrl: socket.socket
    capacity: int
    max_new_cap: int
    pad_to: int = 16
    _last_stats: dict = dataclasses.field(default_factory=dict)
    _waves: int = 0

    def run_wave(self, prompts: np.ndarray, prompt_lens: np.ndarray,
                 max_new, request_ids=None) -> dict:
        prompts = np.asarray(prompts, np.int32)
        B = prompts.shape[0]
        mn = np.broadcast_to(np.asarray(max_new, np.int32), (B,))
        out = _ctrl_call(self.ctrl, {
            "cmd": "run", "prompts": prompts.tolist(),
            "prompt_lens": np.asarray(prompt_lens, np.int32).tolist(),
            "max_new": mn.tolist(), "max_new_cap": self.max_new_cap,
            "request_ids": (list(map(int, request_ids))
                            if request_ids is not None else None)})
        if out.get("cmd") != "result":
            raise RuntimeError(f"pair {self.pair_id}: bad wave reply {out!r}")
        self._last_stats = out
        self._waves += 1
        return out

    def serve(self, reqs) -> list:
        """Drive a request bucket wave-by-wave (the process-backed analogue
        of one pair's share of ``SpecDecodeServer.run``); returns
        :class:`repro.serving.ServeResult` rows."""
        from ..serving.server import ServeResult
        results = []
        t_start = time.perf_counter()
        for w0 in range(0, len(reqs), self.capacity):
            wave = list(reqs[w0:w0 + self.capacity])
            n_real = len(wave)
            while len(wave) < self.capacity:   # pad short waves; extras
                wave.append(wave[-1])          # decode but are dropped
            q = self.pad_to
            maxlen = max(len(r.prompt) for r in wave)
            maxlen = ((maxlen + q - 1) // q) * q
            prompts = np.zeros((self.capacity, maxlen), np.int32)
            lens = np.zeros(self.capacity, np.int32)
            for i, r in enumerate(wave):
                prompts[i, :len(r.prompt)] = r.prompt
                lens[i] = len(r.prompt)
            mn = np.array([r.max_new_tokens for r in wave], np.int32)
            wave_t0 = time.perf_counter() - t_start
            out = self.run_wave(prompts, lens, mn,
                                request_ids=[r.request_id for r in wave])
            wave_t1 = time.perf_counter() - t_start
            tokens = np.asarray(out["tokens"], np.int64)
            produced = np.asarray(out["produced"], np.int64)
            seqs = out.get("acceptance_seqs") or [[]] * self.capacity
            first_tok_s = wave_t0 + float(out.get("prefill_s", 0.0))
            for i in range(n_real):
                r = wave[i]
                n = min(int(produced[i]), self.max_new_cap)
                bits = seqs[i] if i < len(seqs) else []
                results.append(ServeResult(
                    request_id=r.request_id, tokens=tokens[i, :n],
                    ttft_ms=(first_tok_s - r.arrival_s) * 1e3,
                    tpot_ms=(wave_t1 - first_tok_s) * 1e3 / max(1, n - 1),
                    e2e_ms=(wave_t1 - r.arrival_s) * 1e3,
                    acceptance_rate=(sum(bits) / len(bits)) if bits else 0.0,
                    queue_ms=(wave_t0 - r.arrival_s) * 1e3,
                    pair_id=self.pair_id,
                    request_class=r.request_class,
                    slo_ttft_ms=r.slo_ttft_ms,
                    slo_tpot_ms=r.slo_tpot_ms))
        return results

    def stats(self) -> dict:
        return _ctrl_call(self.ctrl, {"cmd": "stats"})

    def summary(self) -> dict:
        """``SpecDecodeServer.pair_summaries``-shaped row for this pair."""
        st = self._last_stats.get("stats", {})
        link = self._last_stats.get("link_stats", {})
        return {"requests": self._waves * self.capacity,
                "iterations": st.get("iterations", 0),
                "acceptance_rate": round(
                    st.get("accepted", 0) / max(1, st.get("proposed", 0)), 4),
                "mode_policy": "distributed", "process": True,
                **{k: link[k] for k in ("bytes_sent", "wire_bytes",
                                        "messages_sent", "transport")
                   if k in link}}

    def shutdown(self) -> None:
        try:
            if self.ctrl is not None:
                _ctrl_call(self.ctrl, {"cmd": "shutdown"})
                self.ctrl.close()
        except Exception:
            pass
        self.ctrl = None
        deadline = time.monotonic() + 10.0
        for p in self.procs:
            while p.poll() is None and time.monotonic() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                p.kill()

    close = shutdown


def holds_tpu() -> bool:
    """True when this process has already brought up JAX's TPU backend
    (checked without initializing any backend)."""
    from jax._src import xla_bridge
    return (xla_bridge.backends_are_initialized()
            and "tpu" in xla_bridge._backends)


def host_tpu_chips() -> int:
    """TPU chips a worker process started from here could open, counted
    without initializing JAX: none when ``JAX_PLATFORMS`` keeps JAX off
    the TPU or the PCI bus has no TPU; otherwise the chip device files
    (``/dev/accel*``, or ``/dev/vfio/<group>``) — a machine handed only
    some of its host's chips lists all of them on the bus but opens only
    its own."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src import hardware_utils
    on_bus = hardware_utils.num_available_tpu_chips_and_device_id()[0]
    if on_bus == 0:
        return 0
    files = len(glob.glob("/dev/accel[0-9]*"))
    if not files and os.path.isdir("/dev/vfio"):
        files = sum(name.isdigit() for name in os.listdir("/dev/vfio"))
    return min(on_bus, files) if files else on_bus


def check_worker_chips(n_workers: int, first_chip: int = 0) -> int:
    """Enforce one process per chip before any worker starts: a chip
    belongs to one process, so on a TPU host the caller must not hold the
    TPU itself and every worker needs a chip of its own (workers
    ``first_chip .. first_chip + n_workers - 1``). Raises
    :class:`repro.topology.TopologyError` at once instead of letting the
    handshake wait out its timeout. Returns the host's chip count (0 =
    the workers run on the CPU)."""
    from ..topology import TopologyError
    chips = host_tpu_chips()
    if chips == 0:
        return 0
    if holds_tpu():
        raise TopologyError(
            "one process per chip: this process already holds the TPU "
            "backend, so worker processes cannot get a chip. Build "
            "process-backed deployments before touching JAX, or serve the "
            "pairs in-process")
    if first_chip + n_workers > chips:
        raise TopologyError(
            f"one process per chip: process-backed pairs need one TPU chip "
            f"per worker process ({first_chip + n_workers} wanted), and "
            f"this host has {chips}")
    return chips


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _bind_chip(env: dict, chip: int) -> dict:
    """Environment that binds one worker process to TPU chip ``chip`` as a
    one-chip slice of its own (the chips-per-process bounds are a subset
    of the host, which libtpu accepts as one load per chip)."""
    port = _free_port()
    return dict(env, TPU_VISIBLE_CHIPS=str(chip),
                TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                TPU_PROCESS_BOUNDS="1,1,1",
                TPU_PROCESS_PORT=str(port),
                TPU_PROCESS_ADDRESSES=f"localhost:{port}")


def spawn_pair(spec, pair, *, model_configs=None, node_params=None,
               workdir=None, timeout_s: Optional[float] = None,
               python: str = sys.executable,
               first_chip: int = 0) -> PairHostHandle:
    """Launch a target host + draft host for one ``process: true`` pair
    on localhost and hand back the driving handle. Topology, overridden
    model configs and overridden node params are written to ``workdir``
    and shipped by path; everything else rebuilds from the spec's seed.
    On a TPU host the target runs on chip ``first_chip`` and the draft on
    the next one (:func:`check_worker_chips` refuses first if that cannot
    be had). ``timeout_s`` bounds every socket wait; by default 120 s,
    or 600 s for a full-width spec, whose hosts first build and compile
    full-size models."""
    import tempfile
    validate_process_pair(spec, pair)
    if timeout_s is None:
        timeout_s = 600.0 if spec.full_width else 120.0
    tpu_host = check_worker_chips(2, first_chip) > 0
    workdir = workdir or tempfile.mkdtemp(prefix=f"dsd-{pair.id}-")
    os.makedirs(workdir, exist_ok=True)
    topo_path = os.path.join(workdir, "topology.json")
    with open(topo_path, "w") as f:
        f.write(spec.to_json())

    cfg_flags = []
    for name, cfg in (model_configs or {}).items():
        path = os.path.join(workdir, f"cfg_{name}.json")
        save_model_config(cfg, path)
        cfg_flags += ["--model-config", f"{name}={path}"]
    for node_id, params in (node_params or {}).items():
        path = os.path.join(workdir, f"params_{node_id}.npz")
        save_params(params, path)
        cfg_flags += ["--node-params", f"{node_id}={path}"]

    env = dict(os.environ)
    src_dir = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    prev = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src_dir + (os.pathsep + prev if prev else "")

    def launch(role, extra, chip):
        err = open(os.path.join(workdir, f"{role}.stderr.log"), "wb")
        return subprocess.Popen(
            [python, "-m", "repro.distributed.host", "--role", role,
             "--topology", topo_path, "--pair", pair.id,
             "--timeout-s", str(timeout_s)] + cfg_flags + extra,
            stdout=subprocess.PIPE, stderr=err,
            env=_bind_chip(env, chip) if tpu_host else env)

    procs = []
    try:
        tgt = launch("target", [], first_chip)
        procs.append(tgt)
        line = _read_line(tgt, "listening port=", 60.0,
                          f"target host ({pair.id})")
        t_port = int(line.split("=", 1)[1])
        drf = launch("draft", ["--connect", f"127.0.0.1:{t_port}"],
                     first_chip + 1)
        procs.append(drf)
        line = _read_line(drf, "listening port=", 60.0,
                          f"draft host ({pair.id})")
        c_port = int(line.split("=", 1)[1])
        _read_line(tgt, "ready", _READY_TIMEOUT_S,
                   f"target host ({pair.id})")
        _read_line(drf, "ready", _READY_TIMEOUT_S,
                   f"draft host ({pair.id})")
        ctrl = socket.create_connection(("127.0.0.1", c_port),
                                        timeout=timeout_s)
        ctrl.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        ctrl.settimeout(max(timeout_s, 600.0))
    except Exception:
        for p in procs:
            p.kill()
        raise
    s = spec.serving
    return PairHostHandle(pair_id=pair.id, procs=procs, ctrl=ctrl,
                          capacity=s.max_batch,
                          max_new_cap=s.max_new_cap or spec.workload.max_new,
                          pad_to=s.pad_to)


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.distributed.host",
        description="Run one side of a draft-target pair in this process.")
    ap.add_argument("--role", required=True, choices=("draft", "target"))
    ap.add_argument("--topology", required=True,
                    help="ClusterSpec JSON path")
    ap.add_argument("--pair", required=True, help="pair id in the topology")
    ap.add_argument("--listen-port", type=int, default=0,
                    help="target: stream listen port; draft: control port "
                         "(0 = ephemeral, printed as 'listening port=N')")
    ap.add_argument("--bind-host", default="127.0.0.1")
    ap.add_argument("--connect", default="",
                    help="draft only: HOST:PORT of the target host "
                         "(default: the target node's address/port)")
    ap.add_argument("--model-config", action="append", default=[],
                    metavar="NAME=PATH",
                    help="override a model name with a ModelConfig JSON")
    ap.add_argument("--node-params", action="append", default=[],
                    metavar="NODE=PATH",
                    help="override a node's params with an npz file")
    ap.add_argument("--timeout-s", type=float, default=120.0)
    args = ap.parse_args(argv)
    try:
        if args.role == "target":
            return run_target(args)
        return run_draft(args)
    except TransportProtocolError as e:
        print(f"[{args.role}-host] protocol error: {e}", file=sys.stderr,
              flush=True)
        return 2


if __name__ == "__main__":
    sys.exit(main())
