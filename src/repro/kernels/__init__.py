"""Pallas TPU kernels. Interpret mode runs them on the CPU for the tests;
on a TPU backend they always compile through Mosaic.

- decode_attn/paged — paged GQA flash-decode; the served path's attention
  for paged sessions on TPU (``models.attention.attention_decode_paged``)
- verify/tree       — fused greedy tree verify (opt-in,
  ``SpecDecodeEngine(use_verify_kernel=True)``)
- verify/verify, decode_attn/decode_attn, ssd — linear-window verify, dense
  flash-decode and the SSD chunked scan; off the served path
"""

import functools as _functools

import jax as _jax


def default_interpret() -> bool:
    """Resolve the kernels' shared ``interpret=None`` auto-default: compile
    for real on TPU backends; the Pallas interpreter elsewhere, which is
    how the CPU tests run the kernels (Mosaic lowers for TPU only)."""
    return _jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def kernel_op(*static_argnames):
    """Shared jit decorator for the public kernel wrappers: every op takes
    an ``interpret=None`` kwarg (resolved inside the pallas_call layer via
    :func:`resolve_interpret`), so ``interpret`` is always static alongside
    the op's own shape/tiling statics."""
    return _functools.partial(_jax.jit,
                              static_argnames=(*static_argnames, "interpret"))

