"""Partition specs for the production mesh (deliverable e).

Mesh axes are fixed by the deployment contract: single-pod ``(data=16,
model=16)``, multi-pod ``(pod=2, data=16, model=16)``. Logical→mesh rules:

- batch            → (pod, data)
- vocab/heads/ffn/experts/ssm-heads → model   (tensor/expert parallel)
- d_model (weights)→ data  (ZeRO-3/FSDP: 2-D weight sharding so the 104-480B
  archs fit 16 GB/chip; XLA inserts the per-layer all-gathers)
- KV-cache: batch→(pod,data), kv_heads→model. When the global batch cannot
  cover the data axis (long_500k, batch=1) the cache *sequence* dim shards
  over data instead (context parallelism).
- uneven dims (40 heads / 16, 8 kv-heads / 16, 24 ssm-heads / 16) rely on
  GSPMD's padded uneven sharding — documented waste, attacked in §Perf.

Implemented as path-pattern rules over the parameter pytree so one table
covers every family.
"""

from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def abstract_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """:class:`jax.sharding.AbstractMesh` over named axes: spec/fit logic
    only needs axis names and sizes, so tests and the dry-run build meshes
    through this helper without devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def _axis_size_of(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return int(mesh.shape[axes])
    return int(np.prod([mesh.shape[a] for a in axes]))


def fit_spec(mesh: Mesh, spec: P, shape: tuple[int, ...]) -> P:
    """Repair a preferred PartitionSpec against a concrete shape.

    pjit input shardings require exact divisibility. For every dim whose
    assigned axis doesn't divide it, the axis is *relocated* to the largest
    currently-unsharded dim it does divide (e.g. qwen3's 40 heads can't
    shard over model=16, so 'model' moves to head_dim=128; whisper's odd
    51865-vocab drops the vocab sharding entirely). Tuple axes degrade to
    the largest dividing sub-axis before relocating.
    """
    out: list = list(spec) + [None] * (len(shape) - len(spec))
    orphans: list = []
    for i, ax in enumerate(out):
        if ax is None:
            continue
        if shape[i] % _axis_size_of(mesh, ax) == 0:
            continue
        placed = False
        if isinstance(ax, tuple):
            # try sub-axes (largest first)
            for sub in sorted(ax, key=lambda a: -mesh.shape[a]):
                if shape[i] % mesh.shape[sub] == 0:
                    out[i] = sub
                    orphans.extend(a for a in ax if a != sub)
                    placed = True
                    break
        if not placed:
            orphans.extend([ax] if isinstance(ax, str) else list(ax))
            out[i] = None
    # relocate orphaned axes onto unsharded dims (largest dims first)
    for ax in orphans:
        size = mesh.shape[ax] if isinstance(ax, str) else _axis_size_of(mesh, ax)
        cands = sorted((j for j in range(len(shape))
                        if out[j] is None and shape[j] % size == 0
                        and shape[j] >= size),
                       key=lambda j: -shape[j])
        if cands:
            out[cands[0]] = ax
    while out and out[-1] is None:
        out.pop()
    return P(*out)


class ShardingRules:
    """Per-arch partition-spec factory bound to a mesh."""

    def __init__(self, mesh: Mesh, cfg: ModelConfig,
                 fsdp_over_pod: bool = True):
        self.mesh = mesh
        self.cfg = cfg
        axes = mesh.axis_names
        self.has_pod = "pod" in axes
        self.dp: Any = (("pod", "data") if self.has_pod else "data")
        # FSDP axis for weight d_model dims
        self.fsdp: Any = (("pod", "data") if (self.has_pod and fsdp_over_pod)
                          else "data")
        self.tp = "model"

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ---------------------------------------------------------------- params

    def param_spec(self, path: str, ndim: int) -> P:
        """Rules keyed on parameter path suffix. Leading ``layers/`` stacking
        axis (or encoder/) is never sharded."""
        fs, tp = self.fsdp, self.tp
        stacked = path.startswith(("layers/", "encoder/"))

        def L(*dims):   # prepend the (unsharded) layer-stack axis
            return P(None, *dims) if stacked else P(*dims)

        leaf = path.split("/")[-1]
        if leaf == "embed":
            # vocab dim UNSHARDED: a vocab-sharded gather's backward is a
            # scatter GSPMD can only handle by replicating the full (V, D)
            # f32 gradient (§Perf cycle 5: 11.7 GiB/device buffers on
            # command-r). Sharding d_model over every axis keeps both the
            # gather and its scatter-grad fully local.
            emb_axes = (("pod", "data", "model") if self.has_pod
                        else ("data", "model"))
            return P(None, emb_axes)               # (V, D)
        if leaf == "lm_head":
            return P(fs, tp)                       # (D, V)
        if leaf in ("final_norm", "enc_norm"):
            return P(None)
        if leaf in ("wq", "wk", "wv"):
            return L(fs, tp, None)                 # (D, H, hd)
        if leaf == "wo":
            return L(tp, None, fs)                 # (H, hd, D)
        if leaf in ("bq", "bk", "bv"):
            return L(tp, None)                     # (H, hd)
        if leaf in ("q_norm", "k_norm"):
            return L(None)
        if leaf in ("ln1", "ln2", "ln_x", "norm"):
            return L(None)
        if leaf in ("w_gate", "w_up"):
            if "moe" in path:
                # experts→model, d_ff→data (Megatron FFN-TP inside each
                # expert): the down-proj contracts the sharded F dim into an
                # activation-sized psum instead of FSDP re-gathering ~2 GB of
                # expert weights per layer (§Perf llama4 cycle)
                return L(tp, None, fs)             # (E, D, F)
            return L(fs, tp)                       # (D, F)
        if leaf == "w_down":
            if "moe" in path:
                return L(tp, fs, None)             # (E, F, D)
            return L(tp, fs)                       # (F, D)
        if leaf == "router":
            return L(fs, tp)                       # (D, E)
        if leaf in ("res_gate", "res_up"):
            return L(fs, tp)
        if leaf == "res_down":
            return L(tp, fs)
        if leaf == "in_proj":
            return L(fs, tp)                       # (D, 2din+2N+nh)
        if leaf == "out_proj":
            return L(tp, fs)                       # (din, D)
        if leaf in ("conv_w",):
            return L(None, tp)                     # (K, C)
        if leaf in ("conv_b",):
            return L(tp)
        if leaf in ("A_log", "D", "dt_bias"):
            return L(tp)                           # (nh,)
        # default: replicate
        return P(*([None] * ndim)) if not stacked else P(None)

    def params_sharding(self, params_shape: Any) -> Any:
        def spec_for(path, leaf):
            pref = self.param_spec(_path_str(path), leaf.ndim)
            return self.named(fit_spec(self.mesh, pref, tuple(leaf.shape)))
        return jax.tree_util.tree_map_with_path(spec_for, params_shape)

    # ----------------------------------------------------------------- data

    def batch_spec(self, global_batch: int) -> Any:
        """Batch axis factor(s) the global batch can actually cover."""
        dp_size = self._axis_size(self.dp)
        if global_batch % dp_size == 0:
            return self.dp
        if self.has_pod and global_batch % self.mesh.shape["pod"] == 0:
            return "pod"
        return None

    def _axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            return self.mesh.shape[axes]
        return int(np.prod([self.mesh.shape[a] for a in axes]))

    def tokens_sharding(self, global_batch: int) -> NamedSharding:
        return self.named(P(self.batch_spec(global_batch), None))

    def frontend_sharding(self, global_batch: int) -> NamedSharding:
        return self.named(P(self.batch_spec(global_batch), None, None))

    def vector_sharding(self, global_batch: int) -> NamedSharding:
        """(B,) vectors: tokens/positions during decode."""
        return self.named(P(self.batch_spec(global_batch)))

    # ---------------------------------------------------------------- caches

    def cache_sharding(self, cache_shape: Any, global_batch: int) -> Any:
        """AttnCache k/v (L,B,S,Hkv,hd), pos_map (L,B,S); SSMCache conv
        (L,B,K-1,C), state (L,B,nh,hd,N); nested for hybrid/encdec."""
        bspec = self.batch_spec(global_batch)
        # context parallelism when the batch can't cover the data axis
        seq_axis = None
        if bspec is None or (bspec == "pod" and not self.has_pod is None):
            seq_axis = "data"
        elif bspec == "pod":
            seq_axis = "data"

        def spec_for(path, leaf):
            name = _path_str(path)
            nd = getattr(leaf, "ndim", 0)
            lf = name.split("/")[-1]
            if nd == 0:   # ring flag etc.
                return self.named(P())
            if lf in ("k", "v", "cross_k", "cross_v") and nd == 5:
                pref = P(None, bspec, seq_axis, self.tp, None)
            elif lf == "pos_map" and nd == 3:
                pref = P(None, bspec, seq_axis)
            elif lf == "conv" and nd == 4:
                pref = P(None, bspec, None, self.tp)
            elif lf == "state" and nd == 5:
                pref = P(None, bspec, self.tp, None, None)
            else:
                pref = P(*([None] * nd))
            return self.named(fit_spec(self.mesh, pref, tuple(leaf.shape)))

        return jax.tree_util.tree_map_with_path(spec_for, cache_shape)

    # ------------------------------------------------------------- trainstate

    def train_state_sharding(self, state_shape: Any, params_sharding: Any
                             ) -> Any:
        """Optimizer moments inherit the param sharding; step replicated."""
        from ..training.train_step import TrainState
        return TrainState(
            params=params_sharding,
            opt=type(state_shape.opt)(
                step=self.named(P()),
                mu=params_sharding,
                nu=params_sharding),
            step=self.named(P()))
