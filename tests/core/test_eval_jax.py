"""The JAX evaluator (one envelope-grouped program; a single circuit is a
suite of one) vs the Python oracle."""
import random

import numpy as np
import pytest

from repro.core import flow
from repro.core.circuits import koios_mac_array, kratos_gemm, sha_like
from repro.core.eval_jax import (_device_vals, _fill_pi_rows,
                                 eval_netlist_jax, plan_netlist,
                                 prepare_suite_program)
from repro.core.flow import _lanes_int
from repro.core.netlist import CONST1, eval_netlist


@pytest.mark.parametrize("mk", [
    lambda: kratos_gemm(m=4, n=4, width=5, sparsity=0.4),
    lambda: koios_mac_array(pes=2, width=4, ctrl_nodes=40),
    lambda: sha_like(rounds=1),
])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_eval_jax_matches_python(mk, use_pallas):
    net = mk()
    rng = random.Random(42)
    NV = 32  # one uint32 lane word
    pi_vals = {s: rng.getrandbits(NV) for s in net.pis}
    ref = eval_netlist(net, pi_vals, NV)
    lanes = {s: np.array([v], dtype=np.uint32) for s, v in pi_vals.items()}
    got = np.asarray(eval_netlist_jax(net, lanes, 1, use_pallas=use_pallas))
    for bus in net.pos.values():
        for s in bus:
            assert int(got[s, 0]) == ref[s] & 0xFFFFFFFF, s


def test_eval_jax_multiword_lanes():
    net = kratos_gemm(m=3, n=3, width=4, sparsity=0.3)
    rng = random.Random(1)
    NW = 4  # 128 test vectors
    lanes = {s: np.array([rng.getrandbits(32) for _ in range(NW)],
                         dtype=np.uint32) for s in net.pis}
    got = np.asarray(eval_netlist_jax(net, lanes, NW))
    # cross-check one lane word against the oracle
    pi_vals = {s: int(lanes[s][2]) for s in net.pis}
    ref = eval_netlist(net, pi_vals, 32)
    for bus in net.pos.values():
        for s in bus:
            assert int(got[s, 2]) == ref[s] & 0xFFFFFFFF


def test_fused_matches_levels_dispatcher():
    """The grouped program gives the oracle's value on every signal of a
    circuit with carry chains, every lane word."""
    net = koios_mac_array(pes=2, width=4, ctrl_nodes=40)
    rng = random.Random(5)
    NW = 2
    lanes = {s: np.array([rng.getrandbits(32) for _ in range(NW)],
                         dtype=np.uint32) for s in net.pis}
    (got,), _ = flow.evaluate_suite([net], [lanes], NW)
    ref = eval_netlist(net, {s: _lanes_int(v) for s, v in lanes.items()},
                       32 * NW)
    for s in range(net.n_signals):
        assert _lanes_int(got[s]) == ref.get(s, 0), s


def test_precompiled_plan_reuse():
    """A prepared suite program evaluates fresh lanes call after call,
    each call equal to a fresh evaluation, with nothing rebuilt."""
    net = kratos_gemm(m=3, n=3, width=4, sparsity=0.3)
    prog = prepare_suite_program([net])
    (g,) = prog.programs
    for seed in (9, 10):
        lanes = flow.random_lanes(net, 1, seed=seed)
        (a,) = prog.run([lanes], 1)
        (b,), _ = flow.evaluate_suite([net], [lanes], 1, program=prog)
        assert np.array_equal(a, eval_netlist_jax(net, lanes, 1))
        assert np.array_equal(a, b)
        assert flow.oracle_check(net, lanes, a, 1)
    assert prog.programs[0] is g


def test_batched_multi_circuit_eval():
    """Different circuits, one vmapped jit: each must match its own
    single-circuit evaluation."""
    nets = [kratos_gemm(m=3, n=3, width=4, sparsity=0.3),
            sha_like(rounds=1),
            koios_mac_array(pes=2, width=4, ctrl_nodes=40)]
    rng = random.Random(3)
    NW = 2
    lanes_list = [{s: np.array([rng.getrandbits(32) for _ in range(NW)],
                               dtype=np.uint32) for s in net.pis}
                  for net in nets]
    outs, _ = flow.evaluate_suite(nets, lanes_list, NW)
    for net, lanes, got in zip(nets, lanes_list, outs):
        single = np.asarray(eval_netlist_jax(net, lanes, NW))
        for bus in net.pos.values():
            for s in bus:
                assert np.array_equal(got[s], single[s]), (net.name, s)


def test_plan_is_width_bucketed():
    """Plans split the level sequence into <= 3 contiguous width buckets
    whose padded volume never exceeds the single worst-case envelope."""
    net = koios_mac_array(pes=2, width=4, ctrl_nodes=40)
    plan = plan_netlist(net)
    assert 1 <= len(plan.buckets) <= 3
    assert sum(bk.n_levels for bk in plan.buckets) == plan.n_levels
    L, M, C, B = plan.envelope
    assert plan.padded_lut_rows + plan.padded_chain_bits \
        <= L * M + L * C * B
    # every real node is represented exactly once
    assert plan.real_luts == net.n_luts
    assert plan.real_chain_bits == net.n_adders


def test_plan_cache_keyed_by_content():
    """Identical structure -> same cached plan object; a structural edit
    (new digest) -> a fresh plan."""
    net = kratos_gemm(m=3, n=3, width=4, sparsity=0.3)
    p1 = plan_netlist(net)
    p2 = plan_netlist(net)
    assert p1 is p2
    net2 = kratos_gemm(m=3, n=3, width=4, sparsity=0.3)
    assert plan_netlist(net2) is p1  # same content, same key
    net2.lut_tt[0] ^= 1
    assert plan_netlist(net2) is not p1


def test_grouped_eval_respects_max_groups_and_matches_single():
    nets = [kratos_gemm(m=3, n=3, width=4, sparsity=0.3),
            sha_like(rounds=1),
            koios_mac_array(pes=2, width=4, ctrl_nodes=40),
            kratos_gemm(m=4, n=4, width=4, sparsity=0.5, seed=7)]
    rng = random.Random(11)
    NW = 1
    lanes_list = [{s: np.array([rng.getrandbits(32)], dtype=np.uint32)
                   for s in net.pis} for net in nets]
    outs, stats = flow.evaluate_suite(nets, lanes_list, NW, max_groups=2)
    assert stats["n_groups"] <= 2
    names = sorted(m for g in stats["groups"] for m in g["members"])
    assert names == sorted(n.name for n in nets)
    for net, lanes, got in zip(nets, lanes_list, outs):
        single = np.asarray(eval_netlist_jax(net, lanes, NW))
        for bus in net.pos.values():
            for s in bus:
                assert np.array_equal(got[s], single[s]), (net.name, s)


def _pi_group():
    """Two circuits of different PI counts in one envelope group, so the
    smaller one's PI slots are padded; one PI of it gets no lanes."""
    nets = [kratos_gemm(m=3, n=3, width=4, sparsity=0.3),
            sha_like(rounds=1)]
    assert len({len(n.pis) for n in nets}) == 2
    rng = np.random.default_rng(4)
    NW = 2
    lanes_list = [{s: rng.integers(0, 2**32, NW, dtype=np.uint32)
                   for s in net.pis} for net in nets]
    small = min(range(2), key=lambda i: len(nets[i].pis))
    del lanes_list[small][nets[small].pis[1]]
    prog = prepare_suite_program(nets, max_groups=1)
    assert prog.groups == [[0, 1]]
    return nets, lanes_list, NW, prog


@pytest.mark.parametrize("mode", ["grouped", "per_circuit"])
def test_device_built_buffer_evaluates_every_signal(mode):
    """The value buffer built on the device from the PI rows alone gives
    the oracle's value on every signal, a PI without lanes reading 0."""
    nets, lanes_list, NW, prog = _pi_group()
    if mode == "grouped":
        outs = prog.run(lanes_list, NW)
    else:
        outs = [np.asarray(eval_netlist_jax(net, lanes, NW))
                for net, lanes in zip(nets, lanes_list)]
    for net, lanes, got in zip(nets, lanes_list, outs):
        assert got.shape == (net.n_signals, NW)
        pi_vals = {s: _lanes_int(lanes[s]) if s in lanes else 0
                   for s in net.pis}
        ref = eval_netlist(net, pi_vals, 32 * NW)
        for s in range(net.n_signals):
            assert _lanes_int(got[s]) == ref.get(s, 0), (net.name, s)


def test_device_built_buffer_equals_host_built():
    """Bit for bit the buffer a host would fill: zeros, CONST1 all ones,
    each PI's lanes at its row; padded PI slots write nothing."""
    nets, lanes_list, NW, prog = _pi_group()
    (g,) = prog.programs
    rows = _fill_pi_rows(g.pi_slots, lanes_list, g.pi_index.shape[1], NW)
    padded = np.asarray(g.pi_index) > g.n_sig
    assert padded.any()
    rows[padded] = 0xDEADBEEF
    got = np.asarray(_device_vals(g.pi_index, rows, n_rows=g.n_sig + 1))
    want = np.zeros((len(nets), g.n_sig + 1, NW), dtype=np.uint32)
    want[:, CONST1] = 0xFFFFFFFF
    for row, lanes in enumerate(lanes_list):
        for s, v in lanes.items():
            want[row, s] = v
    assert np.array_equal(got, want)


@pytest.mark.parametrize("mode", ["grouped", "per_circuit"])
def test_lanes_keyed_off_the_pis_raise(mode):
    """A key that is no primary input has no row to go to."""
    nets, lanes_list, NW, prog = _pi_group()
    lanes_list[0][nets[0].lut_out[0]] = np.ones(NW, dtype=np.uint32)
    with pytest.raises(ValueError, match="not a primary input"):
        if mode == "grouped":
            prog.run(lanes_list, NW)
        else:
            eval_netlist_jax(nets[0], lanes_list[0], NW)


def test_suite_stats_name_the_grouped_program():
    """Whatever ran before, ``evaluate_suite`` runs the envelope-grouped
    program and its stats say so: ``mode``, ``n_groups`` and per group
    the padded LUT rows of its members, which the benchmark reads."""
    nets = [kratos_gemm(m=3, n=3, width=4, sparsity=0.3),
            sha_like(rounds=1)]
    lanes_list = [flow.random_lanes(n, 1, seed=i)
                  for i, n in enumerate(nets)]
    for net, lanes in zip(nets, lanes_list):
        eval_netlist_jax(net, lanes, 1)
    outs, stats = flow.evaluate_suite(nets, lanes_list, 1, max_groups=1)
    assert stats["mode"] == "grouped" and stats["n_groups"] == 1
    (g,) = prepare_suite_program(nets, max_groups=1).programs
    assert stats["groups"][0]["padded_lut_rows"] == sum(
        p.padded_lut_rows for p in g.member_plans)
    assert sum(plan_netlist(n).padded_lut_rows for n in nets) \
        <= stats["groups"][0]["padded_lut_rows"]
    for net, lanes, got in zip(nets, lanes_list, outs):
        assert flow.oracle_check(net, lanes, got, 1)


def test_same_netlist_twice_in_one_suite():
    """One circuit listed twice is two members, each evaluated on its own
    lanes."""
    net = kratos_gemm(m=3, n=3, width=4, sparsity=0.3)
    NW = 2
    lanes_list = [flow.random_lanes(net, NW, seed=s) for s in (1, 2)]
    outs, stats = flow.evaluate_suite([net, net], lanes_list, NW)
    assert sum(len(g["members"]) for g in stats["groups"]) == 2
    assert not np.array_equal(outs[0], outs[1])
    for lanes, got in zip(lanes_list, outs):
        assert np.array_equal(got, eval_netlist_jax(net, lanes, NW))
        assert flow.oracle_check(net, lanes, got, NW)


@pytest.mark.parametrize("NW", [1, 3, 128])
def test_single_and_suite_entries_agree(NW):
    """A circuit alone and the same circuit as a member of a two-circuit
    group give the same value buffer, at lane widths below, across and at
    the kernel's lane tile."""
    nets = [kratos_gemm(m=3, n=3, width=4, sparsity=0.3),
            koios_mac_array(pes=2, width=4, ctrl_nodes=40)]
    lanes_list = [flow.random_lanes(n, NW, seed=7 + i)
                  for i, n in enumerate(nets)]
    outs, stats = flow.evaluate_suite(nets, lanes_list, NW, max_groups=1)
    assert stats["n_groups"] == 1
    for net, lanes, got in zip(nets, lanes_list, outs):
        assert got.shape == (net.n_signals, NW)
        assert np.array_equal(got, eval_netlist_jax(net, lanes, NW))
    assert flow.oracle_check(nets[1], lanes_list[1], outs[1], NW)
