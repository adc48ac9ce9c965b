"""Simulated-annealing placement refinement: determinism, legality, the
never-worse-than-seed guarantee, timing-driven weighting, the placed
timing bit-identity it must preserve, and the registry cache contract.

The contract under test: ``place_ir(refine="anneal")`` returns a grid-
legal placement that is bit-deterministic per (netlist digest, arch
placement key, seed, refine mode), whose wirelength never exceeds the
analytic seed's; the placed vectorized timing path stays bit-identical
to :func:`repro.core.timing.analyze_placed_oracle` on annealed
placements at zero AND nonzero wire delays (the Fig-5/Table-III pins
survive refinement); and every annealer cache — refined placements in
``"placement"``, criticality weights in ``"criticality"`` — lives in the
unified :mod:`repro.core.plan` registry so one ``clear_caches()``
provably drops them (the PR-6 stale-placement regression, re-pinned for
the annealer).
"""
import numpy as np
import pytest

from repro.core.alm import ARCHS, CPS_PER_PS, make_arch
from repro.core.anneal import (ANNEAL_COUNTS, criticality_weights,
                               delay_signature, edge_criticality,
                               refine_placement)
from repro.core.circuit_ir import apply_placement
from repro.core.circuits import kratos_gemm
from repro.core.packing import pack
from repro.core.place import (PLACE_COUNTS, _routed_edges, place_ir,
                              placement_for)
from repro.core.plan import cache_stats, clear_caches
from repro.core.timing import analyze_oracle, analyze_placed_oracle
from repro.core.timing_vec import analyze_ir, build_suite_timing_program


def _wired(arch, w1=25.0, w2=40.0, wl=120.0, **kw):
    return make_arch(arch.name + "_wired", bypass_inputs=arch.bypass_inputs,
                     addmux_fanin=arch.addmux_fanin,
                     lut6=arch.concurrent_6lut,
                     t_wire_hop1=w1, t_wire_hop2=w2, t_wire_long=wl, **kw)


def _ir(net=None, arch=None):
    net = net or kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    arch = arch or ARCHS["dd5"]
    return pack(net, arch).lower_ir()


def _legal(pl, n_lbs):
    assert pl.grid_w * pl.grid_h >= n_lbs
    assert (pl.lb_x >= 0).all() and (pl.lb_x < pl.grid_w).all()
    assert (pl.lb_y >= 0).all() and (pl.lb_y < pl.grid_h).all()
    slots = set(zip(pl.lb_x.tolist(), pl.lb_y.tolist()))
    assert len(slots) == n_lbs, "overlapping LB slots after refinement"


def test_refined_placement_deterministic_legal_never_worse():
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    seed_pl = place_ir(ir, arch, seed=0)
    for backend in ("numpy", "jax"):
        a = place_ir(ir, arch, seed=0, refine="anneal", backend=backend)
        b = place_ir(ir, arch, seed=0, refine="anneal", backend=backend)
        assert np.array_equal(a.lb_x, b.lb_x)
        assert np.array_equal(a.lb_y, b.lb_y)
        _legal(a, ir.n_lbs)
        assert a.refine == "anneal"
        assert (a.grid_w, a.grid_h) == (seed_pl.grid_w, seed_pl.grid_h)
        assert a.wirelength(ir) <= seed_pl.wirelength(ir)
    # distinct seeds explore distinct trajectories
    c = place_ir(ir, arch, seed=1, refine="anneal")
    a = place_ir(ir, arch, seed=0, refine="anneal")
    assert not (np.array_equal(a.lb_x, c.lb_x)
                and np.array_equal(a.lb_y, c.lb_y))


def test_refinement_actually_improves_wirelength():
    """The annealer exists to beat the legalization-limited seed — on a
    real suite member it must strictly improve, not merely tie (the
    17-circuit geomean >= 5% gate lives in benchmarks/anneal_refine)."""
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    seed_pl = place_ir(ir, arch, seed=0)
    ann = place_ir(ir, arch, seed=0, refine="anneal")
    assert ann.wirelength(ir) < seed_pl.wirelength(ir)


def test_trivial_circuits_refine_to_seed():
    """<= 1 LB (or no routed edges): refinement is a no-op, not a crash."""
    from repro.core.circuits import vtr_mixed

    arch = ARCHS["dd5"]
    ir = pack(vtr_mixed(logic_nodes=8, adders=1), arch).lower_ir()
    assert ir.n_lbs == 1
    seed_pl = place_ir(ir, arch, seed=0)
    ann = refine_placement(ir, arch, seed_pl, seed=0)
    assert ann is seed_pl
    assert place_ir(ir, arch, seed=0, refine="anneal").n_lbs == 1


def test_placed_timing_bit_identical_on_annealed_placements():
    """Vectorized placed timing == placed Python oracle, bit for bit, on
    *annealed* placements — zero and nonzero wire delays, both timing
    backends (numpy walk + batched jax program)."""
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    for aname in ("baseline", "dd5"):
        for arch in (ARCHS[aname], _wired(ARCHS[aname])):
            packed = pack(net, arch)
            ir = packed.lower_ir()
            pl = placement_for(ir, arch, seed=0, refine="anneal")
            assert pl.refine == "anneal"
            want = analyze_placed_oracle(packed, pl)
            pir = apply_placement(ir, pl)
            assert analyze_ir(pir, arch) == want
            prog = build_suite_timing_program([pir])
            cp = int(prog.run(arch.delay_table()[None, :])[0, 0])
            assert cp / CPS_PER_PS == want["critical_path_ps"]
            if (arch.t_wire_hop1, arch.t_wire_hop2, arch.t_wire_long) \
                    == (0.0, 0.0, 0.0):
                # Fig-5/Table-III pins: zero wire == unplaced, bitwise
                assert want == analyze_oracle(packed)


def test_timing_driven_mode_weights_and_determinism():
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    crit = edge_criticality(ir, arch)
    assert crit.shape == (ir.fanin_sig.size,)
    assert (crit >= 0.0).all() and (crit <= 1.0).all()
    # some edge sits on the critical path (criticality 1 up to fp dust)
    assert crit.max() > 0.99
    w = criticality_weights(ir, arch, cache=False)
    src, _ = _routed_edges(ir)
    assert w.shape == (src.size,)
    assert (w >= 1.0).all()
    a = place_ir(ir, arch, seed=0, refine="anneal_timing")
    b = place_ir(ir, arch, seed=0, refine="anneal_timing")
    assert np.array_equal(a.lb_x, b.lb_x)
    assert np.array_equal(a.lb_y, b.lb_y)
    _legal(a, ir.n_lbs)
    assert a.refine == "anneal_timing"
    with pytest.raises(ValueError, match="refine mode"):
        place_ir(ir, arch, seed=0, refine="bogus")


def test_delay_signature_excludes_wire_tiers():
    """Criticality weighting may read the delay row but never the wire
    tiers — otherwise one placement could not serve a whole wire-delay
    family and the placement-reuse gate would silently die."""
    arch = ARCHS["dd5"]
    assert delay_signature(arch) == delay_signature(_wired(arch))
    slow_mux = make_arch("dd5_slowmux", bypass_inputs=2, addmux_fanin=10,
                         t_z_to_adder=400.0)
    assert delay_signature(arch) != delay_signature(slow_mux)


def test_refined_placement_cache_keys():
    """Analytic, uniform-annealed and timing-annealed placements are
    distinct registry entries; wire-delay rows share the annealed entry
    (the place-once-per-key reuse), while a different non-wire delay row
    re-anneals only in the timing-driven mode."""
    clear_caches()
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    base = placement_for(ir, arch, seed=0)
    ann = placement_for(ir, arch, seed=0, refine="anneal")
    tim = placement_for(ir, arch, seed=0, refine="anneal_timing")
    assert base.refine is None and ann.refine == "anneal"
    assert cache_stats()["placement"]["size"] == 3
    hits0 = PLACE_COUNTS["cache_hit"]
    assert placement_for(ir, arch, seed=0, refine="anneal") is ann
    # a wire-only delay variant is a cache hit for every refine mode
    wired = _wired(arch)
    assert placement_for(ir, wired, seed=0, refine="anneal") is ann
    assert placement_for(ir, wired, seed=0, refine="anneal_timing") is tim
    assert PLACE_COUNTS["cache_hit"] == hits0 + 3
    # a non-wire delay change re-keys only the timing-driven mode
    slow_mux = make_arch("dd5_slowmux", bypass_inputs=2, addmux_fanin=10,
                         t_z_to_adder=400.0)
    assert placement_for(ir, slow_mux, seed=0, refine="anneal") is ann
    assert placement_for(
        ir, slow_mux, seed=0, refine="anneal_timing") is not tim


def test_anneal_caches_in_registry_cleared_with_everything_else():
    """Regression mirroring the PR-6 placement-cache rule for the new
    annealer caches: refined placements and criticality weights must
    live in the plan registry — after ``clear_caches()`` a re-request
    re-solves (no stale object served) yet reproduces the same values
    (determinism)."""
    clear_caches()
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    n0 = ANNEAL_COUNTS["anneal"]
    c0 = ANNEAL_COUNTS["crit_solve"]
    a = placement_for(ir, arch, seed=0, refine="anneal_timing")
    assert ANNEAL_COUNTS["anneal"] == n0 + 1
    assert ANNEAL_COUNTS["crit_solve"] == c0 + 1
    assert cache_stats()["criticality"]["size"] == 1
    # warm: both caches hit, no new solves
    h0 = ANNEAL_COUNTS["crit_hit"]
    assert placement_for(ir, arch, seed=0, refine="anneal_timing") is a
    criticality_weights(ir, arch)
    assert ANNEAL_COUNTS["crit_hit"] == h0 + 1
    assert ANNEAL_COUNTS["anneal"] == n0 + 1
    clear_caches()
    assert cache_stats()["placement"]["size"] == 0
    assert cache_stats()["criticality"]["size"] == 0
    b = placement_for(ir, arch, seed=0, refine="anneal_timing")
    assert b is not a                       # re-solved, not stale
    assert ANNEAL_COUNTS["anneal"] == n0 + 2
    assert ANNEAL_COUNTS["crit_solve"] == c0 + 2
    assert np.array_equal(a.lb_x, b.lb_x)
    assert np.array_equal(a.lb_y, b.lb_y)


def test_jax_ensemble_no_worse_than_single_chain_seed():
    """The jax multi-chain ensemble keeps the best exact wirelength over
    [seed] + chains, so it can never lose to the analytic seed and its
    result is legal whatever the chains did."""
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    seed_pl = place_ir(ir, arch, seed=0)
    j = place_ir(ir, arch, seed=0, refine="anneal", backend="jax",
                 anneal_chains=2, anneal_steps=24)
    _legal(j, ir.n_lbs)
    assert j.wirelength(ir) <= seed_pl.wirelength(ir)


def test_fixed_point_weights_exact_scaled_and_bounded():
    """The jax ensemble's int32 weights: integral weights stay exact at
    scale 1, fractional ones get 8 fractional bits, fewer when the cost
    bound would leave int32, and a grid no scale fits is refused."""
    from repro.core.anneal import _fixed_point_weights

    wq, scale = _fixed_point_weights(np.ones(7), 4, 5)
    assert scale == 1 and wq.dtype == np.int32 and (wq == 1).all()
    w = np.array([1.0, 1.5, 4.99])
    wq, scale = _fixed_point_weights(w, 4, 5)
    assert scale == 256
    assert np.array_equal(wq, np.round(w * 256).astype(np.int32))
    big = np.full(1000, 1.5)
    wq, scale = _fixed_point_weights(big, 2000, 2000)
    assert 1 <= scale < 256
    assert 4 * int(wq.sum()) * 4000 <= 2 ** 31 - 1
    with pytest.raises(OverflowError):
        _fixed_point_weights(np.ones(10 ** 6), 2000, 2000)


def test_jax_timing_driven_refine_legal_never_worse_deterministic():
    """Timing-driven weights reach the device as fixed point; the host
    re-scores with the exact weights, so the weighted cost never exceeds
    the seed's and repeated runs agree bit for bit."""
    arch = ARCHS["dd5"]
    ir = _ir(arch=arch)
    seed_pl = place_ir(ir, arch, seed=0)
    w = criticality_weights(ir, arch)
    assert not np.array_equal(w, np.round(w))     # fractional weights
    src, dst = _routed_edges(ir)

    def cost(pl):
        return float((w * (np.abs(pl.lb_x[src] - pl.lb_x[dst])
                           + np.abs(pl.lb_y[src] - pl.lb_y[dst]))).sum())

    a = refine_placement(ir, arch, seed_pl, mode="anneal_timing",
                         backend="jax", chains=2, steps=24)
    b = refine_placement(ir, arch, seed_pl, mode="anneal_timing",
                         backend="jax", chains=2, steps=24)
    _legal(a, ir.n_lbs)
    assert cost(a) <= cost(seed_pl)
    assert np.array_equal(a.lb_x, b.lb_x) and np.array_equal(a.lb_y, b.lb_y)
