"""Jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic.  On any other backend
(the CPU runs of the tests) they execute under ``interpret=True``: the
kernel body runs as traced jnp ops, which checks BlockSpec indexing and
kernel logic exactly but says nothing about Mosaic's layout rules — those
are checked by compiling for a described TPU
(``tests/core/test_chip_compile.py``).  ``use_pallas=False`` selects the
jnp references instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .bitplane_matmul import bitplane_matmul as _bitplane_pallas
from .flash_attention import flash_attention as _flash_pallas
from .lut_eval import lut_eval as _lut_pallas
from .lut_eval import lut_eval6 as _lut6_pallas
from .popcount_matmul import popcount_matmul as _popcount_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("mode", "k_bits", "use_pallas"))
def popcount_matmul(x_packed, w_packed, mode="and", k_bits=None,
                    use_pallas=True):
    if use_pallas:
        return _popcount_pallas(x_packed, w_packed, mode=mode, k_bits=k_bits,
                                interpret=not _on_tpu())
    return ref.popcount_matmul_ref(x_packed, w_packed, mode=mode,
                                   k_bits=k_bits)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def lut_eval(inputs, tts, use_pallas=True):
    if use_pallas:
        return _lut_pallas(inputs, tts, interpret=not _on_tpu())
    return ref.lut_eval_ref(inputs, tts)


def lut_eval6(inputs, tt_lo, tt_hi, use_pallas=True):
    """Fused-layout 6-pin LUT kernel (un-jitted: always called from inside
    the fused evaluator's own jit — once per width bucket of the
    multi-scan plan, so ``M`` is the bucket's own envelope, not the
    circuit-wide worst case)."""
    if use_pallas:
        return _lut6_pallas(inputs, tt_lo, tt_hi, interpret=not _on_tpu())
    return ref.lut_eval6_ref(inputs, tt_lo, tt_hi)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def bitplane_matmul(x, planes, scale, use_pallas=True):
    if use_pallas:
        return _bitplane_pallas(x, planes, scale, interpret=not _on_tpu())
    return ref.bitplane_matmul_ref(x, planes, scale)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "use_pallas"))
def flash_attention(q, k, v, causal=True, window=None, softcap=None,
                    scale=None, use_pallas=True):
    if use_pallas:
        return _flash_pallas(q, k, v, causal, window, softcap, scale,
                             not _on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def ssd_scan(x, dt, A, B, C, use_pallas=True):
    if use_pallas:
        return _ssd_pallas(x, dt, A, B, C, interpret=not _on_tpu())
    return ref.ssd_scan_ref(x, dt, A, B, C)
