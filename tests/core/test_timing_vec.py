"""Vectorized timing (PackIR + timing_vec) vs the Python oracle.

The contract is *bit-identity*, not closeness: integer centi-picoseconds,
exact in any summation order, on the host and on the device.  Property
tests fuzz random packed circuits across all three canonical archs; the
regression test pins Fig-5/Table-III-feeding numbers.
"""
import numpy as np
import pytest

from repro.core.alm import ARCHS, CPS_PER_PS, DD5, make_arch
from repro.core.circuits import kratos_gemm, sha_like, vtr_mixed
from repro.core.netlist import CONST1
from repro.core.packing import pack
from repro.core.timing import analyze, analyze_oracle
from repro.core.timing_vec import build_suite_timing_program

from _hypothesis_shim import given, settings, st
from test_flow import random_netlist


@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=5, deadline=None)
def test_vectorized_timing_matches_oracle(seed):
    """numpy-backend analyze() == analyze_oracle(), bit for bit, on
    random packed circuits under every canonical arch (property)."""
    net = random_netlist(seed)
    for arch in ARCHS.values():
        packed = pack(net, arch, seed=seed % 3)
        want = analyze_oracle(packed)
        got = analyze(packed)
        assert got == want, (net.name, arch.name)


def test_jax_program_matches_oracle_batched():
    """The batched lax.scan/vmap program: several circuits stacked on one
    vmap axis, several delay rows on the other — every (circuit, arch)
    critical path bit-identical to the oracle."""
    nets = [random_netlist(3), random_netlist(11),
            kratos_gemm(m=4, n=4, width=4, sparsity=0.5)]
    # same structural class: dd5 and a fan-in-20 variant (delays differ,
    # packs are identical) — the pack-once-retime-many property
    archs = [DD5, make_arch("dd5_f20", bypass_inputs=2, addmux_fanin=20,
                            z_sources=40)]
    assert archs[0].structural_key() == archs[1].structural_key()
    packs = [pack(n, archs[0], seed=0) for n in nets]
    prog = build_suite_timing_program([p.lower_ir() for p in packs])
    cps = prog.run(np.stack([a.delay_table() for a in archs]))
    assert cps.shape == (len(nets), len(archs))
    for g, net in enumerate(nets):
        for k, arch in enumerate(archs):
            want = analyze_oracle(pack(net, arch, seed=0))
            assert cps[g, k] / CPS_PER_PS == want["critical_path_ps"], (
                net.name, arch.name)


# (critical_path_ps, alms, area_mwta, adp) pinned before the PackIR
# refactor, re-pinned once when timing moved to integer centi-picoseconds:
# the critical paths moved by float rounding only (at most 3.7e-12 ps),
# alms and area not at all
_PINS = {
    ("gemm-fu", "baseline"): (8252.09, 397, 2958444.0, 24413346147.96),
    ("gemm-fu", "dd5"): (7996.33, 283, 2187367.6751999995,
                         17490913762.23201),
    ("gemm-fu", "dd6"): (8536.33, 283, 2199599.388, 18776506243.766037),
    ("sha", "baseline"): (3213.03, 64, 476928.0, 1532383971.8400002),
    ("sha", "dd5"): (3161.45, 64, 494669.72159999993, 1563873591.3523197),
    ("sha", "dd6"): (3341.45, 64, 497435.904, 1662157201.4207997),
    ("or1200-like", "baseline"): (7916.89, 86, 640872.0, 5073713128.08),
    ("or1200-like", "dd5"): (8316.7, 68, 525586.5791999999,
                             4371145903.232639),
    ("or1200-like", "dd6"): (8396.7, 67, 520753.212, 4372608495.2004),
}


@pytest.mark.parametrize("arch_name", ["baseline", "dd5", "dd6"])
def test_regression_pinned_fig5_table3_numbers(arch_name):
    """The figure-feeding metrics must not move across the PackIR
    refactor: vectorized analyze() reproduces the pre-refactor oracle
    values exactly (seed-0 packs of Fig-5/Table-III representatives)."""
    for mk in (lambda: kratos_gemm(m=6, n=6, width=6, sparsity=0.5),
               lambda: sha_like(rounds=1),
               lambda: vtr_mixed(logic_nodes=200, adders=3)):
        net = mk()
        rec = analyze(pack(net, ARCHS[arch_name], seed=0))
        cp, alms, area, adp = _PINS[(net.name, arch_name)]
        assert rec["critical_path_ps"] == cp
        assert rec["alms"] == alms
        assert rec["area_mwta"] == area
        assert rec["adp"] == adp


def test_pack_ir_columns_consistent():
    """PackIR column sanity: per-signal site/LB columns agree with the
    packed object graph, the fanin CSR covers every LUT input and chain
    operand edge, and level tables place each node once."""
    net = random_netlist(7)
    packed = pack(net, DD5, seed=0)
    ir = packed.lower_ir()
    assert ir.n_signals == net.n_signals
    # sites
    for li, out in enumerate(net.lut_out):
        assert ir.sig_site[out] == packed.lut_site.get(li, -2)
    for ci, ch in enumerate(net.chains):
        for bi, s in enumerate(ch.sums):
            assert ir.sig_site[s] == packed.chain_site.get((ci, bi), -2)
    # LB column derives from the site
    for s in range(ir.n_signals):
        site = int(ir.sig_site[s])
        want_lb = packed.alm_lb[site] if site >= 0 else -1
        assert ir.sig_lb[s] == want_lb
    # CSR: every non-const LUT input appears as a fanin edge of its output
    for li, out in enumerate(net.lut_out):
        lo, hi = int(ir.fanin_ptr[out]), int(ir.fanin_ptr[out + 1])
        edges = set(ir.fanin_sig[lo:hi].tolist())
        want = {s for s in net.lut_inputs[li] if s > CONST1}
        assert edges == want
    for ch in net.chains:
        for bi, s in enumerate(ch.sums):
            lo, hi = int(ir.fanin_ptr[s]), int(ir.fanin_ptr[s + 1])
            edges = set(ir.fanin_sig[lo:hi].tolist())
            want = {q for q in (ch.a[bi], ch.b[bi]) if q > CONST1}
            if bi == 0 and ch.cin > CONST1:
                want.add(ch.cin)
            assert edges == want
    # each placed node appears in exactly one level row
    outs = [o for lv in ir.lut_levels for o in lv.out.tolist()]
    assert len(outs) == len(set(outs))
    n_lut_rows = sum(lv.out.shape[0] for lv in ir.lut_levels)
    placed_luts = sum(1 for li in range(net.n_luts)
                      if packed.lut_site.get(li) is not None)
    assert n_lut_rows == placed_luts
    assert sum(lv.cout.shape[0] for lv in ir.chain_levels) == len(net.chains)


def test_timing_wall_accounting():
    from repro.core import timing

    timing.reset_timing_wall()
    net = random_netlist(1)
    analyze(pack(net, ARCHS["baseline"], seed=0))
    w = timing.read_timing_wall()
    assert w["calls"] == 1 and w["s"] > 0.0


def test_jax_program_refuses_int32_overflow():
    """Device arrivals are int32 centi-ps: a delay row whose longest
    formable path could pass 2**31 is refused instead of wrapping."""
    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    prog = build_suite_timing_program([pack(net, DD5, seed=0).lower_ir()])
    assert prog.run(DD5.delay_table()[None, :]).shape == (1, 1)
    slow = make_arch("slow", bypass_inputs=2, t_route_global=1e7)
    with pytest.raises(OverflowError):
        prog.run(slow.delay_table()[None, :])
