"""Pallas kernels: bit-parallel k-LUT level evaluation.

The functional simulator (``core/eval_jax.py``) evaluates topological levels
of LUTs over packed test-vector lanes.  Per LUT the output is a
sum-of-minterms over its input lanes — identical bitwise work for all LUTs
in a level, so it vectorizes across (LUT, lane) tiles.  The truth tables
ride along as a 2-D ``[M, w]`` operand (one row of uint32 words per LUT),
blocked ``(BM, w)`` beside the ``(BM, K, BN)`` lane block: a 1-D ``[M]``
operand blocked ``(BM,)`` is refused by Mosaic whenever ``M > BM``, since
XLA tiles the 1-D array differently from the block (e.g. ``T(1024)``
against ``T(256)``).  ``M`` need not be a multiple of ``BM``: the last
row tile is partial, its out-of-range rows are never written back.

Two entry points:

* :func:`lut_eval` — the legacy per-level kernel, K <= 5, one uint32 table
  per LUT (kept for the per-level dispatcher and the kernel sweep tests).
* :func:`lut_eval6` — the fused-evaluator kernel.  Levels are padded to a
  uniform ``[M, 6, N]`` layout; the 64-entry table arrives as two uint32
  words and pin 5 Shannon-selects between them, so the inner loop is the
  same 32-minterm unroll as the 5-input case with one extra select.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_M = 256   # LUTs per tile
BLOCK_N = 128   # lane words per tile


def _kernel(tt_ref, in_ref, o_ref, *, k: int):
    # tt_ref: [BM, 1] uint32; in_ref: [BM, k, BN] uint32; o_ref: [BM, BN]
    tts = tt_ref[...]
    ins = in_ref[...]
    BM, _, BN = ins.shape
    out = jnp.zeros((BM, BN), dtype=jnp.uint32)
    full = jnp.uint32(0xFFFFFFFF)
    for m in range(1 << k):  # unrolled: 2^k <= 32 minterms
        bit = (tts >> jnp.uint32(m)) & jnp.uint32(1)
        term = jnp.full((BM, BN), full, dtype=jnp.uint32)
        for j in range(k):
            lane = ins[:, j, :]
            term = term & (lane if (m >> j) & 1 else ~lane)
        out = out | (jnp.where(bit == 1, full, jnp.uint32(0)) & term)
    o_ref[...] = out


def lut_eval(inputs: jax.Array, tts: jax.Array,
             interpret: bool = True) -> jax.Array:
    """``inputs[M, K, N]`` uint32 lanes + ``tts[M]`` -> ``out[M, N]``."""
    M, K, N = inputs.shape
    assert K <= 5
    bm = min(BLOCK_M, M)
    bn = min(BLOCK_N, N)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn))
    return pl.pallas_call(
        functools.partial(_kernel, k=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, K, bn), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.uint32),
        interpret=interpret,
    )(tts.astype(jnp.uint32)[:, None], inputs.astype(jnp.uint32))


def _kernel6(tt_ref, in_ref, o_ref):
    # tt_ref: [BM, 2] uint32 (lo, hi); in_ref: [BM, 6, BN]; o_ref: [BM, BN]
    tt = tt_ref[...]
    lo_t = tt[:, 0:1]
    hi_t = tt[:, 1:2]
    ins = in_ref[...]
    BM, _, BN = ins.shape
    lo = jnp.zeros((BM, BN), dtype=jnp.uint32)
    hi = jnp.zeros((BM, BN), dtype=jnp.uint32)
    full = jnp.uint32(0xFFFFFFFF)
    for m in range(32):  # unrolled minterms over pins 0..4
        term = jnp.full((BM, BN), full, dtype=jnp.uint32)
        for j in range(5):
            lane = ins[:, j, :]
            term = term & (lane if (m >> j) & 1 else ~lane)
        lo_bit = (lo_t >> jnp.uint32(m)) & jnp.uint32(1)
        hi_bit = (hi_t >> jnp.uint32(m)) & jnp.uint32(1)
        lo = lo | (jnp.where(lo_bit == 1, full, jnp.uint32(0)) & term)
        hi = hi | (jnp.where(hi_bit == 1, full, jnp.uint32(0)) & term)
    sel = ins[:, 5, :]
    o_ref[...] = (sel & hi) | (~sel & lo)


def lut_eval6(inputs: jax.Array, tt_lo: jax.Array, tt_hi: jax.Array,
              interpret: bool = True) -> jax.Array:
    """``inputs[M, 6, N]`` uint32 lanes + split 64-bit tables -> ``out[M, N]``.

    Pin 5 Shannon-decomposes the 6-input table: ``tt_lo`` covers pin5=0
    minterms, ``tt_hi`` pin5=1.  LUTs narrower than 6 inputs are expressed
    by replicating their table into both words and padding unused pins
    with constant-0 lanes.
    """
    M, K, N = inputs.shape
    assert K == 6
    bm = min(BLOCK_M, M)
    bn = min(BLOCK_N, N)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn))
    return pl.pallas_call(
        _kernel6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 6, bn), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.uint32),
        interpret=interpret,
    )(jnp.stack([tt_lo.astype(jnp.uint32), tt_hi.astype(jnp.uint32)], axis=1),
      inputs.astype(jnp.uint32))
