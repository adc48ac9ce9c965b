"""The unified CircuitIR substrate: one lowering serves eval, timing and
equivalence.

Property tests prove, from a SINGLE lowering per (circuit, structural
class): (a) fused evaluation bit-identical to the ``eval_netlist``
oracle, (b) timing bit-identical to ``analyze_oracle``, (c) identical
columns from fresh vs template-incremental lowering — across
baseline/DD5/DD6 plus cluster-geometry grid points.  Instrumentation
tests pin the no-duplicate-lowering property of ``sweep_suite`` and the
unified cache registry's invalidation semantics (the old
``clear_plan_caches`` left sweep templates live — regression)."""
import dataclasses

import numpy as np
import pytest

from repro.core import flow
from repro.core.alm import ARCHS, make_arch
from repro.core.circuit_ir import (CircuitIR, LOWER_COUNTS,
                                   lower_netlist_ir, lower_pack_ir,
                                   lower_pack_ir_incremental,
                                   read_lower_counts, reset_lower_counts)
from repro.core.circuits import kratos_gemm, sha_like
from repro.core.eval_jax import (clear_plan_caches, eval_netlist_jax,
                                 plan_from_ir, plan_netlist)
from repro.core.netlist import CONST0, CONST1, Netlist
from repro.core.packing import pack
from repro.core.plan import cache_stats, clear_caches
from repro.core.repack import pack_prefix, repack
from repro.core.sweep import sweep_suite
from repro.core.timing import analyze_oracle
from repro.core.timing_vec import analyze_ir

from _hypothesis_shim import given, settings, st
from test_flow import random_netlist

#: baseline/DD5/DD6 plus two cluster-geometry grid points — every
#: structural class the property tests lower through one prefix
ARCH_POINTS = [
    ARCHS["baseline"],
    ARCHS["dd5"],
    ARCHS["dd6"],
    make_arch("dd5_a8", bypass_inputs=2, alms_per_lb=8),
    make_arch("b0_i48", bypass_inputs=0, lb_inputs=48),
]


def _assert_same_ir(a: CircuitIR, b: CircuitIR):
    for f in dataclasses.fields(CircuitIR):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if f.name in ("lut_levels", "chain_levels"):
            assert len(va) == len(vb)
            for x, y in zip(va, vb):
                for g in dataclasses.fields(type(x)):
                    assert np.array_equal(getattr(x, g.name),
                                          getattr(y, g.name)), \
                        (f.name, g.name)
        elif isinstance(va, np.ndarray):
            assert np.array_equal(va, vb), f.name
        else:
            assert va == vb, f.name


@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=3, deadline=None)
def test_one_lowering_serves_eval_and_timing(seed):
    """The tentpole property: a single CircuitIR per (circuit,
    structural class) drives (a) fused evaluation equal to the python
    oracle, (b) timing bit-identical to ``analyze_oracle``, and (c)
    fresh and template-incremental lowering produce identical columns —
    across the canonical archs and cluster-geometry points."""
    net = random_netlist(seed)
    prefix = pack_prefix(net, seed=0)
    lanes = flow.random_lanes(net, 2, seed=seed)
    template = None
    for arch in ARCH_POINTS:
        packed = repack(prefix, arch)
        ir = lower_pack_ir(packed)
        # (c) incremental lowering parity, every column
        if template is not None:
            _assert_same_ir(ir, lower_pack_ir_incremental(packed, template))
        template = ir
        # (a) the same IR object plans the evaluation the netlist gets,
        # tensor for tensor, and that evaluation == python oracle
        got, want = plan_from_ir(ir), plan_netlist(net)
        assert got.flags == want.flags and got.n_signals == want.n_signals
        for bk_got, bk_want in zip(got.arrays(), want.arrays()):
            for x, y in zip(bk_got, bk_want):
                assert np.array_equal(x, y), arch.name
        vals = eval_netlist_jax(net, lanes, 2, use_pallas=False)
        assert flow.oracle_check(net, lanes, vals, 2), arch.name
        # (b) timing from the same IR object == python oracle, bit for bit
        want = analyze_oracle(packed)
        got = analyze_ir(ir, arch)
        assert got == want, arch.name


def test_functional_ir_serves_eval_of_const_fed_luts():
    """Constant operands are kept verbatim in the IR columns (the old
    packed lowering zeroed them): a LUT reading CONST1 and a chain with
    a CONST1 cin must evaluate exactly like the python oracle."""
    from repro.core.netlist import eval_netlist

    net = Netlist("constfed")
    a, b = net.add_pi_bus("in", 2)
    l1 = net.add_lut((a, CONST1, b), 0b10010110)      # parity with a 1
    l2 = net.add_lut((CONST0, l1), 0b0100)            # l1 & ~0
    sums, cout = net.add_chain([l1, l2], [b, CONST1], cin=CONST1,
                               want_cout=True)
    net.set_po_bus("s", sums)
    net.set_po_bus("c", [cout])
    lanes = flow.random_lanes(net, 2, seed=3)
    vals = np.asarray(eval_netlist_jax(net, lanes, 2, use_pallas=False))
    assert flow.oracle_check(net, lanes, vals, 2)
    # and the same functional IR's timing view under a pack stays exact
    for arch in (ARCHS["baseline"], ARCHS["dd5"]):
        packed = pack(net, arch, seed=0)
        assert analyze_ir(packed.lower_ir(), arch) == analyze_oracle(packed)


def test_sweep_suite_lowers_once_per_circuit_and_class():
    """Counter-instrumented no-duplicate-lowering property: a sweep over
    C circuits and K structural classes runs exactly C functional
    lowerings and C*K placement patches; a warm re-run (same caches)
    adds none."""
    clear_caches()
    reset_lower_counts()
    nets = [kratos_gemm(m=4, n=4, width=4, sparsity=0.5), random_netlist(6)]
    grid = [ARCHS["baseline"], ARCHS["dd5"],
            make_arch("g_a8", bypass_inputs=2, alms_per_lb=8)]
    packs: dict = {}
    programs: dict = {}
    prefixes: dict = {}
    res = sweep_suite(nets, grid, backend="numpy", packs=packs,
                      programs=programs, prefixes=prefixes)
    counts = read_lower_counts()
    assert counts["functional"] == len(nets)
    assert (counts["placement_full"] + counts["placement_incremental"]
            == len(nets) * res.n_classes)
    # the warm path re-lowers nothing at all
    sweep_suite(nets, grid, backend="numpy", packs=packs,
                programs=programs, prefixes=prefixes)
    assert read_lower_counts() == counts


def test_clear_caches_forces_relowering():
    """Regression (the cache-clearing bug): ``eval_jax.clear_plan_caches``
    used to leave the sweep's prefix-held IR templates live, so a
    "cleared" state could still patch from a stale template.  The unified
    registry drops templates too: after ``clear_caches()`` a sweep with
    warm prefixes must re-lower from scratch — and produce identical
    records."""
    clear_caches()
    nets = [random_netlist(11)]
    grid = [ARCHS["baseline"], ARCHS["dd5"]]
    prefixes: dict = {}
    res1 = sweep_suite(nets, grid, backend="numpy", prefixes=prefixes)
    assert len(prefixes) == 1
    prefix = next(iter(prefixes.values()))
    assert prefix.ir_template is not None     # template cached in registry
    reset_lower_counts()
    clear_plan_caches()                       # the old entry point — now
    assert prefix.ir_template is None         # reaches the templates too
    assert all(st["size"] == 0 for st in cache_stats().values())
    res2 = sweep_suite(nets, grid, backend="numpy", prefixes=prefixes)
    counts = read_lower_counts()
    assert counts["functional"] == 1          # forced full re-lowering
    assert counts["placement_full"] >= 1
    for g in range(len(nets)):
        for k in range(len(grid)):
            assert (res1.records[g][k]["critical_path_ps"]
                    == res2.records[g][k]["critical_path_ps"])


def test_ir_templates_are_seed_keyed():
    """A template lowered under one placement seed must never serve a
    prefix at another seed (the registry key carries the seed)."""
    clear_caches()
    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    p0 = pack_prefix(net, seed=0)
    p0.ir_template = repack(p0, ARCHS["dd5"]).lower_ir()
    assert p0.ir_template is not None
    p1 = pack_prefix(net, seed=1)
    assert p1.ir_template is None


def test_plan_cache_cleared_by_unified_registry():
    """``plan_netlist`` results live in the registry: identical content
    hits, and ``clear_caches()`` forces a rebuild."""
    net = kratos_gemm(m=3, n=3, width=4, sparsity=0.3)
    p1 = plan_netlist(net)
    assert plan_netlist(net) is p1
    clear_caches()
    assert plan_netlist(net) is not p1


def test_functional_ir_is_content_cached_and_shared():
    """One functional IR per content digest serves both eval planning and
    packed lowering — the netlist-shaped arrays of a packed IR are the
    functional IR's arrays (no copy, no re-levelization)."""
    clear_caches()
    reset_lower_counts()
    net = sha_like(rounds=1)
    func = lower_netlist_ir(net)
    assert lower_netlist_ir(net) is func
    plan_netlist(net)
    packed_ir = pack(net, ARCHS["dd5"], seed=0).lower_ir()
    assert read_lower_counts()["functional"] == 1
    assert packed_ir.fanin_sig is func.fanin_sig
    assert packed_ir.po_sig is func.po_sig
    for ll_p, ll_f in zip(packed_ir.lut_levels, func.lut_levels):
        assert ll_p.ins is ll_f.ins and ll_p.tt_lo is ll_f.tt_lo


@pytest.mark.parametrize("arch_name", ["baseline", "dd5"])
def test_vector_cone_closure_matches_python_ints(arch_name):
    """The vectorized residue-cone closure (cones extracted into
    standalone netlists, evaluated through the unified evaluator over
    all 2^W assignments) agrees with the python-int enumeration entry
    for entry, and still catches corruption."""
    import random

    from repro.core.equiv import exhaustive_residue_report, reelaborate

    rng = random.Random(1)
    net = Netlist("wide")
    ins = net.add_pi_bus("in", 14)
    a_ops, b_ops = [], []
    for i in range(6):
        la = net.add_lut(tuple(rng.sample(ins, 4)), rng.getrandbits(16))
        lb = net.add_lut(tuple(rng.sample(ins, 4)), rng.getrandbits(16))
        a_ops.append(la)
        b_ops.append(lb)
        net.set_po_bus(f"keep{i}", [la, lb])   # fanout > 1: no absorption
    sums, cout = net.add_chain(a_ops, b_ops, want_cout=True)
    net.set_po_bus("s", sums)
    net.set_po_bus("c", [cout])
    re_elab = reelaborate(pack(net, ARCHS[arch_name], seed=0))
    residue = [("lut", i) for i in range(net.n_luts)] \
        + [("chain", i) for i in range(len(net.chains))]
    rv = exhaustive_residue_report(net, re_elab, residue,
                                   vector_min_support=1)
    rp = exhaustive_residue_report(net, re_elab, residue,
                                   vector_min_support=99)
    assert rv["vector_cones"] > 0
    assert rv["proven_cones"] == rp["proven_cones"] == len(residue)
    assert rv["unclosed"] == rp["unclosed"]
    assert rv["mismatches"] == rp["mismatches"]
    # corruption must fail through the vector path too
    re_elab.phys.lut_tt[0] ^= 1
    bad = exhaustive_residue_report(net, re_elab, residue,
                                    vector_min_support=1)
    assert bad["mismatches"]


def test_cone_extraction_pi_leaf_raises_keyerror():
    """Regression: a cone leaf that is a PI outside the support must
    raise KeyError (the unclosed-cone signal callers catch and fall back
    on), not fall through the driver dispatch into the chain branch and
    crash with IndexError."""
    from repro.core.equiv import _extract_cone_netlist

    net = Netlist("pileaf")
    a, b, c = net.add_pi_bus("in", 3)
    o = net.add_lut((a, b, c), 0b10010110)
    net.set_po_bus("po", [o])
    with pytest.raises(KeyError):
        _extract_cone_netlist(net, [o], [a, b])   # c is outside the cut


def test_lower_counts_are_plain_ints():
    reset_lower_counts()
    assert read_lower_counts() == {k: 0 for k in LOWER_COUNTS}
