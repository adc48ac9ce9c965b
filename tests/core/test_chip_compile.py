"""The flow's device programs compile for a TPU v5e at suite widths.

Nothing here runs on a chip: each test compiles one device program for a
*described* v5e (``jax.experimental.topologies``) from shapes alone, so
what the TPU compiler refuses — a Mosaic layout, a dtype, a program too
large — fails here instead of on the chip.  The shapes are the real ones
of the generated Kratos + Koios + VTR suites at their default sizes.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

#: ``lut_eval6`` row counts of the suite's widest evaluator buckets
#: (conv2d-fu 2330 and 576, conv1d-fu 942, the 257 just past one tile)
SUITE_BUCKET_M = (257, 576, 942, 2330)
LANE_WORDS = (4, 8, 128)

#: the suite's widest circuit, whose envelope the timing test compiles
WIDEST = "conv2d-fu"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", prev)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def suite_nets():
    from repro.core.circuits import koios_suite, kratos_suite, vtr_suite

    return kratos_suite() + koios_suite() + vtr_suite()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("n_words", LANE_WORDS)
@pytest.mark.parametrize("m", SUITE_BUCKET_M)
def test_lut_eval6_compiles_for_v5e(one_chip, m, n_words):
    from repro.kernels.lut_eval import lut_eval6

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    compiled = jax.jit(
        lambda ins, lo, hi: lut_eval6(ins, lo, hi, interpret=False)
    ).lower(u32(m, 6, n_words), u32(m), u32(m)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", (257, 2330))
def test_lut_eval_compiles_for_v5e(one_chip, m):
    """The k <= 5 kernel shares the 2-D truth-table layout."""
    from repro.kernels.lut_eval import lut_eval

    compiled = jax.jit(
        lambda ins, tts: lut_eval(ins, tts, interpret=False)
    ).lower(jax.ShapeDtypeStruct((m, 5, 4), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((m,), jnp.uint32, sharding=one_chip)
            ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_words", (4, 128))
def test_grouped_eval_compiles_for_v5e(one_chip, suite_nets, n_words,
                                       monkeypatch):
    """The vmapped multi-scan evaluator at the suite's widest envelope
    group (the one holding conv2d-fu), with the Mosaic kernel inside."""
    from repro.core.eval_jax import _run_fused_batch, prepare_suite_program
    from repro.kernels import ops

    # the default backend here is the CPU, which would interpret the
    # kernel; the program is built for the described TPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    prog = prepare_suite_program(suite_nets)
    names = [[prog.names[i] for i in g] for g in prog.groups]
    gi = next(i for i, g in enumerate(names) if WIDEST in g)
    n_sig, stacked, flags, _ = prog.programs[gi]
    vals = jax.ShapeDtypeStruct((len(prog.groups[gi]), n_sig + 1, n_words),
                                jnp.uint32, sharding=one_chip)
    compiled = _run_fused_batch.lower(
        vals, _shapes(stacked, one_chip), flags=flags,
        use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def widest_pack(suite_nets):
    from repro.core.alm import ARCHS
    from repro.core.packing import pack

    net = next(n for n in suite_nets if n.name == WIDEST)
    return pack(net, ARCHS["dd5"], seed=0)


def test_timing_program_compiles_for_v5e(one_chip, widest_pack):
    """The batched timing scan at the conv2d-fu envelope, three arch
    rows (baseline, DD5, DD6)."""
    from repro.core.alm import ARCHS
    from repro.core.timing_vec import (build_suite_timing_program,
                                       delay_components)

    ir = widest_pack.lower_ir()
    assert ir.n_signals > 20000
    prog = build_suite_timing_program([ir])
    tables = np.stack([a.delay_table() for a in ARCHS.values()])
    comps = delay_components(tables)
    args = _shapes((prog._tensors, prog._po)
                   + tuple(comps[k].astype(np.int32)
                           for k in ("edge", "wire", "lut", "chain")),
                   one_chip)
    prog._build_jit().lower(*args).compile()


def test_anneal_ensemble_compiles_for_v5e(one_chip, widest_pack):
    """The jax multi-chain annealer on the conv2d-fu grid, at the
    default schedule (4 chains, size-scaled steps and moves)."""
    from repro.core.anneal import (_adjacency, _anneal_ensemble,
                                   _anneal_inputs, _default_moves,
                                   _default_steps, _fixed_point_weights)
    from repro.core.place import _routed_edges, place_ir

    ir = widest_pack.lower_ir()
    seed_pl = place_ir(ir, widest_pack.arch, 0)
    L = seed_pl.n_lbs
    src, dst = _routed_edges(ir)
    w = np.ones(src.size, dtype=np.float64)
    ptr, nbr, wts = _adjacency(L, src, dst, w)
    x0 = seed_pl.lb_x.astype(np.int64)
    y0 = seed_pl.lb_y.astype(np.int64)
    W, H = seed_pl.grid_w, seed_pl.grid_h
    wq, scale = _fixed_point_weights(w, W, H)
    nbr_pad, w_pad, occ0, streams = _anneal_inputs(
        ptr, nbr, wts, x0, y0, W, H, seed_pl.net_digest,
        seed_pl.placement_key, 0, _default_steps(L), _default_moves(L),
        0.05, 4, scale)
    ints = (nbr_pad, w_pad, src, dst, wq, x0, y0, occ0) + streams
    args = _shapes(tuple(np.asarray(a, np.int32) for a in ints), one_chip)
    _anneal_ensemble.lower(*args, W=W, H=H).compile()
