"""The served path's Pallas kernels compile for a TPU v5e at the widths the
chip smoke serves (qwen2.5-3b: Hkv=2, G=8, head_dim 128; windows of
γ_max+1 = 13 and single-token decode; KV pages of 16; batch 4).

No chip is needed: the TPU compiler is installed and compiles against a
described, unattached ``v5e:2x2`` topology. This catches what interpret
mode cannot — block shapes the Mosaic tiling rule refuses, VMEM overuse —
and the compiled text must carry the kernel as a ``tpu_custom_call``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn.paged import paged_decode_attention
from repro.kernels.verify.ops import tree_verify_fused

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
