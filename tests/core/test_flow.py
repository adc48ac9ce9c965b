"""Property-style tests for the unified flow pipeline: envelope-grouped /
width-bucketed evaluation must be bit-exact against the ``eval_netlist``
Python oracle and against the old single-envelope path, on random circuits
and across all three architectures."""
import random

import numpy as np
import pytest

from repro.core import flow
from repro.core.alm import ARCHS
from repro.core.circuits import kratos_gemm, sha_like
from repro.core.equiv import reelaborate
from repro.core.eval_jax import (eval_netlist_jax, group_plans_by_envelope,
                                 grouping_padded_value_rows, plan_netlist)
from repro.core.netlist import CONST0, CONST1, Netlist
from repro.core.packing import pack

from _hypothesis_shim import given, settings, st


def random_netlist(seed: int) -> Netlist:
    """LUT cloud + carry chains + post-chain logic (deep enough to have a
    non-trivial level-width profile)."""
    rng = random.Random(seed)
    net = Netlist(f"rand{seed}")
    pool = list(net.add_pi_bus("in", rng.randint(8, 16)))
    for _ in range(rng.randint(10, 40)):
        k = rng.randint(1, 6)
        ins = rng.sample(pool, min(k, len(pool)))
        pool.append(net.add_lut(tuple(ins), rng.getrandbits(1 << len(ins))))
    for c in range(rng.randint(1, 3)):
        w = rng.randint(2, 10)
        a = [rng.choice(pool) for _ in range(w)]
        b = [rng.choice(pool) for _ in range(w)]
        cin = rng.choice([CONST0, CONST1, rng.choice(pool)])
        sums, cout = net.add_chain(a, b, cin=cin,
                                   want_cout=rng.random() < 0.5)
        pool.extend(sums)
        net.set_po_bus(f"s{c}", sums)
        if cout is not None:
            net.set_po_bus(f"c{c}", [cout])
    for _ in range(rng.randint(5, 15)):
        k = rng.randint(2, 5)
        ins = rng.sample(pool, min(k, len(pool)))
        pool.append(net.add_lut(tuple(ins), rng.getrandbits(1 << len(ins))))
    net.set_po_bus("po", pool[-min(8, len(pool)):])
    return net.sweep()


def _oracle_po_match(net, lanes, vals, n_lane_words):
    return flow.oracle_check(net, lanes, vals, n_lane_words)


@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=4, deadline=None)
def test_bucketed_eval_matches_oracle(seed):
    """Single-circuit bucketed multi-scan == Python oracle (property).
    The jnp kernel path keeps the fuzz loop's compile cost low; the
    pallas kernel itself is proven in test_eval_jax / test_kernels."""
    net = random_netlist(seed)
    lanes = flow.random_lanes(net, 2, seed=seed)
    vals = eval_netlist_jax(net, lanes, 2, use_pallas=False)
    assert _oracle_po_match(net, lanes, vals, 2)


@pytest.mark.slow
@given(seed=st.integers(min_value=0, max_value=10_000),
       max_groups=st.integers(min_value=1, max_value=3))
@settings(max_examples=3, deadline=None)
def test_grouped_eval_matches_oracle_and_single_envelope(seed, max_groups):
    """Envelope-grouped suite eval == oracle == old single-envelope path,
    for any group budget (property)."""
    nets = [random_netlist(seed + i) for i in range(4)]
    lanes = [flow.random_lanes(n, 2, seed=seed + 100 + i)
             for i, n in enumerate(nets)]
    outs, stats = flow.evaluate_suite(nets, lanes, 2, max_groups=max_groups,
                                      use_pallas=False)
    assert stats["n_groups"] <= max_groups
    # the old single-worst-case-envelope path: one group, one bucket
    outs_single, _ = flow.evaluate_suite(nets, lanes, 2, max_groups=1,
                                         max_buckets=1, use_pallas=False)
    for net, ln, got, ref in zip(nets, lanes, outs, outs_single):
        assert np.array_equal(got, ref), net.name
        assert _oracle_po_match(net, ln, got, 2)


@pytest.mark.parametrize("arch_name", ["baseline", "dd5", "dd6"])
def test_grouped_eval_of_reelaborations_matches_oracle(arch_name):
    """The suite-scale use case: per-arch re-elaborated physical netlists
    evaluated as one grouped program, proven against the oracle."""
    nets = [random_netlist(s) for s in (3, 7, 11)]
    phys = [reelaborate(pack(n, ARCHS[arch_name], seed=0)).phys
            for n in nets]
    lanes = [flow.random_lanes(p, 1, seed=i) for i, p in enumerate(phys)]
    outs, stats = flow.evaluate_suite(phys, lanes, 1, max_groups=2)
    assert stats["n_groups"] <= 2
    for p, ln, got in zip(phys, lanes, outs):
        assert _oracle_po_match(p, ln, got, 1)


def test_suite_compiles_to_few_groups():
    """A real mixed suite clusters into <= 4 envelope groups (the
    one-jit-program-per-group property of the suite-scale flow)."""
    nets = [kratos_gemm(m=3, n=3, width=4, sparsity=0.3),
            kratos_gemm(m=4, n=4, width=5, sparsity=0.5, seed=2),
            sha_like(rounds=1),
            random_netlist(5),
            random_netlist(9)]
    plans = [plan_netlist(n) for n in nets]
    groups = group_plans_by_envelope(plans, max_groups=4)
    assert len(groups) <= 4
    assert sorted(i for g in groups for i in g) == list(range(len(nets)))


def test_size_aware_grouping_isolates_giant_value_buffer():
    """A circuit with a huge signal count but a tiny level envelope must
    not be co-located with small circuits (its group mates would pad
    their value buffers to the giant's row count).  The signal-count
    merge term isolates it; the volume-only cost (signal_weight=0) is
    the old behavior and groups it."""
    giant = Netlist("giant")
    pis = giant.add_pi_bus("in", 3000)          # many signals, ...
    o = giant.add_lut((pis[0], pis[1], pis[2]), 0b10010110)
    giant.set_po_bus("po", [o])                 # ... near-empty envelope
    smalls = [random_netlist(s) for s in (1, 2, 3)]
    plans = [plan_netlist(n) for n in [giant] + smalls]
    g_old = group_plans_by_envelope(plans, max_groups=2, signal_weight=0.0)
    g_new = group_plans_by_envelope(plans, max_groups=2)
    assert [0] in g_new, f"giant not isolated: {g_new}"
    rows_old = grouping_padded_value_rows(plans, g_old)
    rows_new = grouping_padded_value_rows(plans, g_new)
    assert rows_new["padded_rows"] < rows_old["padded_rows"]
    assert rows_new["padded_rows"] >= rows_new["real_rows"]
    # grouping still covers every plan exactly once
    assert sorted(i for g in g_new for i in g) == list(range(len(plans)))


def test_bucketed_plan_cuts_padding_waste():
    """On a wide-then-narrow profile the bucketed plan must waste fewer
    padded rows than the single worst-case envelope."""
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.4)
    p = plan_netlist(net)
    real = p.real_luts + p.real_chain_bits
    padded_bucketed = p.padded_lut_rows + p.padded_chain_bits
    L, M, C, B = p.envelope
    padded_single = L * M + L * C * B
    assert 1 <= len(p.buckets) <= 3
    assert padded_bucketed < padded_single
    assert real <= padded_bucketed


def test_pack_and_analyze_matches_direct_flow():
    """flow.pack_and_analyze == seed-averaged pack+analyze by hand."""
    from repro.core.timing import analyze

    net = random_netlist(1)
    seeds = (0, 1)
    rec = flow.pack_and_analyze(net, "dd5", seeds=seeds)
    want = {}
    for s in seeds:
        r = analyze(pack(net, ARCHS["dd5"], seed=s))
        for k in ("alms", "area_mwta", "adp"):
            want[k] = want.get(k, 0.0) + r[k] / len(seeds)
    for k, v in want.items():
        assert rec[k] == pytest.approx(v)


def test_run_circuit_equiv_gate():
    """The flow's equivalence gate proves (and records) pack equivalence."""
    net = random_netlist(2)
    out = flow.run_circuit(net, ("baseline", "dd5"), seeds=(0,),
                           check_equiv=True)
    for arch, rec in out.items():
        assert rec["equivalent"], arch
        assert rec["equiv_method"] in ("symbolic", "simulate")


def test_ratios_vs_baseline():
    per_arch = {
        "baseline": {"area_mwta": 100.0, "critical_path_ps": 10.0,
                     "adp": 1000.0},
        "dd5": {"area_mwta": 80.0, "critical_path_ps": 11.0, "adp": 880.0},
    }
    r = flow.ratios_vs_baseline(per_arch)
    assert r == {"dd5": {"area_mwta": 0.8, "critical_path_ps": 1.1,
                         "adp": 0.88}}
