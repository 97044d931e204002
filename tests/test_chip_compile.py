"""The served path's Pallas kernels compile for a TPU v5e at the widths the
chip smoke serves (qwen2.5-3b: Hkv=2, G=8, head_dim 128; windows of
γ_max+1 = 13 and single-token decode; KV pages of 16; batch 4).

No chip is needed: the TPU compiler is installed and compiles against a
described, unattached ``v5e:2x2`` topology. This catches what interpret
mode cannot — block shapes the Mosaic tiling rule refuses, VMEM overuse —
and the compiled text must carry the kernel as a ``tpu_custom_call``.

The step programs' compiled text is checked too: the stacked dense KV
cache is loop state that XLA updates in place, so no loop body of the
colocated step or the transport's propose / verify copies or restacks it.
"""

import collections
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.core.engine import SpecDecodeEngine
from repro.core.specdec import SpecDecodeState
from repro.kernels.decode_attn.paged import paged_decode_attention
from repro.kernels.verify.ops import tree_verify_fused
from repro.models.model import build_model

B, HKV, G, HD, BS, NB, NLOG = 4, 2, 8, 128, 16, 40, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T,quant", [(13, False), (13, True), (1, False)],
                         ids=["verify-bf16", "verify-int8", "decode-bf16"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, T, quant):
    kv = jnp.int8 if quant else jnp.bfloat16
    scale = _shape(one_chip, (NB, BS, HKV), jnp.float32) if quant else None
    args = (_shape(one_chip, (B, T, HKV, G, HD), jnp.bfloat16),
            _shape(one_chip, (NB, BS, HKV, HD), kv),
            _shape(one_chip, (NB, BS, HKV, HD), kv), scale, scale,
            _shape(one_chip, (NB, BS), jnp.int32),
            _shape(one_chip, (B, NLOG), jnp.int32),
            _shape(one_chip, (B, T), jnp.int32))

    def attend(q, k, v, ks, vs, pm, tbl, qpos):
        return paged_decode_attention(q, k, v, ks, vs, pm, tbl, qpos,
                                      length=NLOG * BS, interpret=False)

    text = jax.jit(attend).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("V", [50280, 151936],
                         ids=["vocab-unified", "vocab-qwen"])
def test_tree_verify_kernels_compile_for_v5e(one_chip, V):
    T = 13
    i32 = jnp.int32
    args = (_shape(one_chip, (B, T), i32),
            _shape(one_chip, (B, T, V), jnp.float32),
            _shape(one_chip, (T,), i32), _shape(one_chip, (T,), i32),
            _shape(one_chip, (T,), jnp.bool_),
            _shape(one_chip, (T, T), jnp.bool_))

    def verify(tok, logits, parent, tpos, valid, mask):
        return tree_verify_fused(tok, logits, parent, tpos, valid, mask,
                                 interpret=False)

    text = jax.jit(verify).lower(*args).compile().as_text()
    # the argmax sweep and the accept rule are two kernels
    assert text.count("tpu_custom_call") >= 2


# ------------------------------------------------------- cache threading

# qwen2.5-shaped heads (Hkv 2; head_dim 64 draft, 128 target) at small depth
CACHE_DRAFT = ModelConfig(name="d", arch_type="dense", n_layers=4,
                          d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
                          d_ff=512, vocab=1024, qkv_bias=True,
                          tie_embeddings=True, remat=False)
CACHE_TARGET = ModelConfig(name="t", arch_type="dense", n_layers=6,
                           d_model=512, n_heads=8, n_kv_heads=2,
                           head_dim=128, d_ff=1024, vocab=1024,
                           qkv_bias=True, tie_embeddings=True, remat=False)
SLOTS, CONTEXT, GAMMA_MAX, MAX_NEW, SYNC = 8, 1024, 12, 64, 8

_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                 r"([\w\-]+)\(")
_CALL = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                   r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _stack_ops(text, stacks):
    """The compiled text's uses of the cache stacks ``stacks`` (a set of
    (HLO dtype, element count)): ``ops``, (opcode, stack, in_loop,
    in_entry) of every instruction whose result is such a stack, an
    instruction being in a loop when a while body reaches its computation
    (fusions and calls included); and ``carries``, per while loop, how
    many of each stack its state holds."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _HEAD.match(line)
        if m:
            cur, comps[m.group(2)] = m.group(2), []
            entry = m.group(2) if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)

    def stack(dtype, dims):
        key = (dtype, math.prod(int(d) for d in dims.split(",") if d))
        return key if key in stacks else None

    def callees(name):
        out = set()
        for line in comps[name]:
            out.update(_CALL.findall(line))
            for group in _BRANCHES.findall(line):
                out.update(c.strip().lstrip("%") for c in group.split(","))
        return out

    whiles = [line for lines in comps.values() for line in lines
              if " while(" in line]
    carries = [collections.Counter(
        filter(None, (stack(*m) for m in re.findall(
            r"(\w+)\[([\d,]*)\]", line.split(" while(")[0]))))
        for line in whiles]
    todo = [b for line in whiles for b in re.findall(r"body=%?([\w.\-]+)",
                                                      line)]
    looped = set()
    while todo:
        c = todo.pop()
        if c in comps and c not in looped:
            looped.add(c)
            todo.extend(callees(c))
    ops = []
    for name, lines in comps.items():
        for line in lines:
            m = _OP.match(line)
            if m and stack(m.group(1), m.group(2)):
                ops.append((m.group(3), stack(m.group(1), m.group(2)),
                            name in looped, name == entry))
    return ops, carries


@pytest.fixture(scope="module")
def step_programs(one_chip):
    """Compiled text of the colocated fused step and the transport's
    propose and verify_commit, with the (HLO dtype, element count) of each
    side's stacked K/V and pos_map, from ``init_cache``."""
    def put(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    key = put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dp, tp = (put(jax.eval_shape(build_model(c).init_params, key))
              for c in (CACHE_DRAFT, CACHE_TARGET))
    eng = SpecDecodeEngine(CACHE_DRAFT, CACHE_TARGET, draft_params=dp,
                           target_params=tp, temperature=0.0,
                           gamma_max=GAMMA_MAX)
    dc = put(jax.eval_shape(lambda: eng.draft.init_cache(SLOTS, CONTEXT)))
    tc = put(jax.eval_shape(lambda: eng.target.init_cache(SLOTS, CONTEXT)))
    state = SpecDecodeState(draft_cache=dc, target_cache=tc,
                            last_token=sds((SLOTS,)), pos=sds((SLOTS,)))
    row, buf, stats = sds((SLOTS,)), sds((SLOTS, MAX_NEW)), sds((SYNC, SLOTS))
    done, scalar = sds((SLOTS,), bool), sds(())
    dw, tw = eng.split_workers()
    calls = {
        "fused": (eng._fused_step(GAMMA_MAX), dp, tp, state, key, scalar,
                  scalar, buf, row, stats, stats, row, done, scalar),
        "propose": (dw.propose(GAMMA_MAX), dp, dc, row, row, key),
        "verify_commit": (tw.verify_commit(GAMMA_MAX), tp, tc,
                          sds((SLOTS, GAMMA_MAX + 1)), row, scalar, key,
                          buf, row, stats, stats, row, done, scalar,
                          scalar),
    }
    texts = {name: fn.lower(*args).compile().as_text()
             for name, (fn, *args) in calls.items()}
    hlo = {"bfloat16": "bf16", "int32": "s32"}
    stacks = {side: {name: (hlo[getattr(c, name).dtype.name],
                            math.prod(getattr(c, name).shape))
                     for name in ("k", "pos_map")}
              for side, c in (("draft", dc), ("target", tc))}
    return texts, stacks


@pytest.mark.parametrize("program", ["fused", "propose", "verify_commit"])
def test_stacked_cache_is_updated_in_place(step_programs, program):
    """The γ scan and the layer loop carry each cache stack once, as one
    buffer that the window's write updates in place: no loop holds a
    second, restacked copy, and no loop body copies a stack. The fused
    step's ENTRY copies no stack either: its donated state aliases its
    output."""
    texts, stacks = step_programs
    ops, carries = _stack_ops(texts[program], {
        s for side in stacks.values() for s in side.values()})
    assert carries, "no loop carries a cache stack"
    for carried in carries:
        for side in stacks.values():
            assert carried[side["k"]] <= 2          # K and V
            assert carried[side["pos_map"]] <= 1
    assert [op for op in ops if op[0] == "copy" and op[2]] == []
    if program == "fused":
        assert [op for op in ops if op[0] == "copy" and op[3]] == []
