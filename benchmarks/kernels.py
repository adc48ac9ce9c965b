"""Kernel microbenchmarks (interpret mode on CPU — correctness-level timing;
real TPU numbers come from the roofline analysis of the compiled dry-run)."""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .common import emit


def _time(fn, *args, iters=3, **kw):
    fn(*args, **kw).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def main():
    r = np.random.default_rng(0)

    x = jnp.asarray(r.integers(0, 2**32, (256, 8), dtype=np.uint32))
    w = jnp.asarray(r.integers(0, 2**32, (256, 8), dtype=np.uint32))
    us = _time(ops.popcount_matmul, x, w, mode="and")
    emit("kernel/popcount_matmul_256x256x256b", us, "mode=and")

    xf = jnp.asarray(r.standard_normal((128, 256)).astype(np.float32))
    planes = jnp.asarray(r.integers(0, 2, (4, 256, 128)).astype(np.float32))
    scale = jnp.ones(128, jnp.float32)
    us = _time(ops.bitplane_matmul, xf, planes, scale)
    emit("kernel/bitplane_matmul_128x256x128_b4", us, "planes=4")

    q = jnp.asarray(r.standard_normal((1, 4, 256, 64)).astype(np.float32))
    k = jnp.asarray(r.standard_normal((1, 2, 256, 64)).astype(np.float32))
    v = jnp.asarray(r.standard_normal((1, 2, 256, 64)).astype(np.float32))
    us = _time(ops.flash_attention, q, k, v)
    emit("kernel/flash_attention_b1h4s256d64", us, "causal_gqa")

    xs = jnp.asarray(r.standard_normal((1, 256, 2, 32)).astype(np.float32))
    dt = jnp.asarray((0.01 + 0.02 * r.random((1, 256, 2))).astype(np.float32))
    A = jnp.asarray(np.full(2, -1.0, np.float32))
    B = jnp.asarray(r.standard_normal((1, 256, 16)).astype(np.float32))
    C = jnp.asarray(r.standard_normal((1, 256, 16)).astype(np.float32))
    us = _time(ops.ssd_scan, xs, dt, A, B, C)
    emit("kernel/ssd_scan_b1l256h2p32", us, "chunked")


if __name__ == "__main__":
    main()
