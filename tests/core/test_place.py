"""Grid placement + routed-wire timing: determinism, legality, cache
semantics and bit-identity of the placed timing path.

The contract under test: placements are deterministic per (netlist
content digest, arch placement key, seed) and legal (one LB per slot);
the placed vectorized timing path — numpy and the batched jax program —
is bit-identical to :func:`repro.core.timing.analyze_placed_oracle`
across baseline/DD5/DD6; at all-zero wire-tier delays the placed path
reproduces the placement-free timing bit for bit (so every Fig-5 /
Table-III pin in ``test_timing_vec`` keeps gating this PR's refactor);
and the placement cache lives in the unified :mod:`repro.core.plan`
registry (the PR-5 stale-template regression, re-pinned for placements).
"""
import numpy as np

from repro.core.alm import ARCHS, CPS_PER_PS, make_arch
from repro.core.circuit_ir import (TIER_HOP1, TIER_LONG, TIER_NONE,
                                   apply_placement)
from repro.core.circuits import kratos_gemm
from repro.core.packing import pack
from repro.core.place import (PLACE_COUNTS, GridPlacement, channel_congestion,
                              grid_shape, lb_connectivity, place_and_apply,
                              place_ir, placement_for)
from repro.core.plan import cache_stats, clear_caches
from repro.core.sweep import oracle_parity, sweep_suite
from repro.core.timing import (analyze, analyze_oracle, analyze_placed_oracle)
from repro.core.timing_vec import analyze_ir, build_suite_timing_program

from test_flow import random_netlist


def _wired(arch, w1=25.0, w2=40.0, wl=120.0, **kw):
    """Same structural class as ``arch``, nonzero wire-tier delays."""
    return make_arch(arch.name + "_wired", bypass_inputs=arch.bypass_inputs,
                     addmux_fanin=arch.addmux_fanin,
                     lut6=arch.concurrent_6lut,
                     t_wire_hop1=w1, t_wire_hop2=w2, t_wire_long=wl, **kw)


CANONICAL = ("baseline", "dd5", "dd6")


def test_placer_deterministic_per_digest_key_seed():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    arch = ARCHS["dd5"]
    ir = pack(net, arch).lower_ir()
    a = place_ir(ir, arch, seed=3)
    b = place_ir(ir, arch, seed=3)
    assert np.array_equal(a.lb_x, b.lb_x)
    assert np.array_equal(a.lb_y, b.lb_y)
    assert a.placement_key == arch.placement_key()
    # a different seed starts a different scatter (distinct rng stream)
    c = place_ir(ir, arch, seed=4)
    assert not (np.array_equal(a.lb_x, c.lb_x)
                and np.array_equal(a.lb_y, c.lb_y))


def test_legalized_placements_respect_grid_capacity():
    nets = [kratos_gemm(m=4, n=4, width=5, sparsity=0.5), random_netlist(7)]
    for net in nets:
        for aname in CANONICAL:
            arch = ARCHS[aname]
            ir = pack(net, arch).lower_ir()
            for backend in ("numpy", "jax"):
                pl = place_ir(ir, arch, seed=0, backend=backend)
                assert pl.grid_w * pl.grid_h >= ir.n_lbs
                assert (pl.lb_x >= 0).all() and (pl.lb_x < pl.grid_w).all()
                assert (pl.lb_y >= 0).all() and (pl.lb_y < pl.grid_h).all()
                slots = set(zip(pl.lb_x.tolist(), pl.lb_y.tolist()))
                assert len(slots) == ir.n_lbs, \
                    f"{net.name}@{aname}/{backend}: overlapping LB slots"


def test_grid_shape_aspect():
    w, h = grid_shape(12, aspect=1.0)
    assert w * h >= 12
    w2, h2 = grid_shape(12, aspect=4.0)
    assert w2 > h2 and w2 * h2 >= 12
    assert grid_shape(0) == (0, 0)


def test_grid_shape_degenerate_inputs_clamp_explicitly():
    """Regression for the degenerate-grid satellite: the width clamp is
    explicit, not incidental rounding — a 1-LB circuit lands on a 1x1
    grid at ANY aspect (round(sqrt(16)) = 4 used to mint a 4-wide grid
    of empty columns), extreme aspects never exceed n_lbs columns, and
    capacity always covers the circuit."""
    import pytest

    for aspect in (1 / 16, 0.5, 1.0, 4.0, 16.0, 1000.0):
        assert grid_shape(1, aspect) == (1, 1)
    for n in (1, 2, 3, 5, 7, 12, 97):
        for aspect in (1 / 16, 0.5, 1.0, 4.0, 16.0):
            w, h = grid_shape(n, aspect)
            assert 1 <= w <= n
            assert w * h >= n
            assert w * (h - 1) < n      # h is minimal for this w
    with pytest.raises(ValueError, match="aspect"):
        grid_shape(4, 0.0)
    with pytest.raises(ValueError, match="aspect"):
        grid_shape(4, -1.0)


def test_extreme_aspect_placement_stays_legal_end_to_end():
    """A 1-LB circuit and an extreme-aspect arch both place, refine and
    time without tripping the legalizer's capacity check."""
    from repro.core.circuits import vtr_mixed

    tiny = vtr_mixed(logic_nodes=8, adders=1)
    wide = make_arch("dd5_wide", bypass_inputs=2, addmux_fanin=10,
                     grid_aspect=16.0)
    for net in (tiny, kratos_gemm(m=4, n=4, width=4, sparsity=0.5)):
        packed = pack(net, wide)
        ir = packed.lower_ir()
        for refine in (None, "anneal"):
            pl = place_ir(ir, wide, seed=0, refine=refine)
            assert pl.grid_w * pl.grid_h >= ir.n_lbs
            assert pl.grid_w <= max(ir.n_lbs, 1)
            slots = set(zip(pl.lb_x.tolist(), pl.lb_y.tolist()))
            assert len(slots) == ir.n_lbs
            assert analyze(packed, placement=pl) \
                == analyze_placed_oracle(packed, pl)


def test_lb_connectivity_symmetric_no_self_edges():
    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    ir = pack(net, ARCHS["baseline"]).lower_ir()
    A = lb_connectivity(ir)
    assert A.shape == (ir.n_lbs, ir.n_lbs)
    assert np.array_equal(A, A.T)
    assert np.trace(A) == 0.0


def test_placed_timing_bit_identical_to_placed_oracle():
    """Vectorized placed timing == placed Python oracle, bit for bit
    (==, not allclose), across the canonical archs, both backends."""
    nets = [kratos_gemm(m=4, n=4, width=5, sparsity=0.5), random_netlist(3)]
    for net in nets:
        for aname in CANONICAL:
            arch = _wired(ARCHS[aname])
            packed = pack(net, arch)
            ir = packed.lower_ir()
            pl = placement_for(ir, arch, seed=0)
            want = analyze_placed_oracle(packed, pl)
            pir = apply_placement(ir, pl)
            got = analyze_ir(pir, arch)
            assert got == want, f"{net.name}@{aname} numpy"
            prog = build_suite_timing_program([pir])
            cp = int(prog.run(arch.delay_table()[None, :])[0, 0])
            assert cp / CPS_PER_PS == want["critical_path_ps"], \
                f"{net.name}@{aname} jax"
            # wire delay can only lengthen paths
            assert want["critical_path_ps"] >= \
                analyze_oracle(packed)["critical_path_ps"]


def test_zero_wire_delay_reproduces_unplaced_timing_bitwise():
    """The refactor's regression contract: with all-zero wire-tier
    delays (every canonical arch), the placed path returns today's
    numbers bit for bit — which is what keeps the Fig-5/Table-III pins
    of ``test_timing_vec`` green through this PR."""
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    for aname in CANONICAL:
        arch = ARCHS[aname]
        assert (arch.t_wire_hop1, arch.t_wire_hop2, arch.t_wire_long) \
            == (0.0, 0.0, 0.0)
        packed = pack(net, arch)
        pl = placement_for(packed.lower_ir(), arch, seed=0)
        base = analyze_oracle(packed)
        assert analyze_placed_oracle(packed, pl) == base
        assert analyze(packed, placement=pl) == base


def test_apply_placement_fills_hop_columns_consistently():
    net = kratos_gemm(m=4, n=4, width=5, sparsity=0.5)
    arch = ARCHS["dd5"]
    ir = pack(net, arch).lower_ir()
    assert not ir.placed
    assert not ir.fanin_hop.any()
    pir = place_and_apply(ir, arch, seed=0)
    assert pir.placed and pir.grid_w > 0 and pir.placement_seed == 0
    assert pir.fanin_hop.any(), "a multi-LB circuit must route some edge"
    assert pir.fanin_hop.max() <= TIER_LONG
    # per-signal coords match the placement of the producing LB
    pl = placement_for(ir, arch, seed=0)
    placed = ir.sig_lb >= 0
    assert np.array_equal(pir.sig_x[placed], pl.lb_x[ir.sig_lb[placed]])
    assert np.array_equal(pir.sig_y[placed], pl.lb_y[ir.sig_lb[placed]])
    assert (pir.sig_x[~placed] == -1).all()
    # level-table hops agree with a direct recomputation from coords
    for ll in pir.lut_levels:
        if not ll.out.size:
            continue
        src_lb = ir.sig_lb[ll.ins]
        dst_lb = ir.sig_lb[ll.out][:, None]
        routed = (src_lb >= 0) & (dst_lb >= 0) & (src_lb != dst_lb)
        d = (np.abs(pl.lb_x[np.clip(src_lb, 0, None)]
                    - pl.lb_x[np.clip(dst_lb, 0, None)])
             + np.abs(pl.lb_y[np.clip(src_lb, 0, None)]
                      - pl.lb_y[np.clip(dst_lb, 0, None)]))
        assert (ll.hop[~routed] == TIER_NONE).all()
        assert (ll.hop[routed & (d == 1)] == TIER_HOP1).all()
        assert (ll.hop[routed] >= TIER_HOP1).all()


def test_placement_cache_in_registry_cleared_with_everything_else():
    """Regression mirroring the PR-5 stale-sweep-template bug: the
    placement cache must live in the unified registry so the single
    ``clear_caches()`` provably drops placements too — a 'cleared' state
    must re-solve, not serve a stale placement object."""
    clear_caches()
    n0 = PLACE_COUNTS["analytic"]
    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    arch = ARCHS["dd5"]
    ir = pack(net, arch).lower_ir()
    a = placement_for(ir, arch, seed=0)
    assert PLACE_COUNTS["analytic"] == n0 + 1
    assert cache_stats()["placement"]["size"] == 1
    # warm hit: same object, no new solve
    assert placement_for(ir, arch, seed=0) is a
    assert PLACE_COUNTS["analytic"] == n0 + 1
    clear_caches()
    assert cache_stats()["placement"]["size"] == 0
    b = placement_for(ir, arch, seed=0)
    assert b is not a                      # re-solved, not stale
    assert PLACE_COUNTS["analytic"] == n0 + 2
    # determinism makes the re-solve identical in value
    assert np.array_equal(a.lb_x, b.lb_x)
    assert np.array_equal(a.lb_y, b.lb_y)


def test_placement_key_shared_across_wire_delay_rows():
    """Wire-tier delays are data, not placement inputs: all delay rows
    of a structural class x grid aspect share ONE cached placement (the
    reuse the >= 2x sweep gate measures), while a different grid aspect
    is a different key."""
    clear_caches()
    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    arch = ARCHS["dd5"]
    wired = _wired(arch)
    assert arch.placement_key() == wired.placement_key()
    ir = pack(net, arch).lower_ir()
    a = placement_for(ir, arch, seed=0)
    hits0 = PLACE_COUNTS["cache_hit"]
    assert placement_for(ir, wired, seed=0) is a
    assert PLACE_COUNTS["cache_hit"] == hits0 + 1
    wide = _wired(arch, grid_aspect=2.0)
    assert wide.placement_key() != arch.placement_key()
    b = placement_for(ir, wide, seed=0)
    assert b is not a and b.grid_w != a.grid_w


def test_sweep_place_matches_placed_oracle_and_frontier():
    """``sweep_suite(place=True)`` over a grid crossing structural
    classes x wire profiles: every record bit-identical to the placed
    oracle under the shared registry placements; zero-wire rows equal
    the unplaced sweep bit for bit."""
    clear_caches()
    nets = [kratos_gemm(m=4, n=4, width=4, sparsity=0.5)]
    grid = [ARCHS["baseline"], _wired(ARCHS["baseline"]),
            ARCHS["dd5"], _wired(ARCHS["dd5"])]
    res = sweep_suite(nets, grid, backend="numpy", place=True)
    assert oracle_parity(res, nets, grid, place=True)
    res0 = sweep_suite(nets, grid, backend="numpy", place=False)
    for k, arch in enumerate(grid):
        placed_cp = res.records[0][k]["critical_path_ps"]
        flat_cp = res0.records[0][k]["critical_path_ps"]
        if (arch.t_wire_hop1, arch.t_wire_hop2, arch.t_wire_long) \
                == (0.0, 0.0, 0.0):
            assert placed_cp == flat_cp
        else:
            assert placed_cp >= flat_cp


def test_mismatched_placement_is_rejected():
    import pytest

    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    arch = ARCHS["dd5"]
    packed = pack(net, arch)
    ir = packed.lower_ir()
    bad = GridPlacement(1, 1, np.zeros(1, np.int32), np.zeros(1, np.int32),
                        0, ir.net_digest, arch.placement_key())
    if ir.n_lbs != 1:
        with pytest.raises(ValueError):
            apply_placement(ir, bad)
        with pytest.raises(ValueError):
            analyze_placed_oracle(packed, bad)
    other = ARCHS["baseline"]
    if other.structural_key() != arch.structural_key():
        with pytest.raises(ValueError):
            place_ir(ir, other, seed=0)


def test_channel_congestion_totals_match_hpwl():
    """RUDY invariant: every net's summed channel demand equals its
    HPWL (vertical demand sums to horizontal span, and vice versa)."""
    net = kratos_gemm(m=4, n=4, width=4, sparsity=0.5)
    arch = ARCHS["dd5"]
    pir = place_and_apply(pack(net, arch).lower_ir(), arch, seed=0)
    cong = channel_congestion(pir, arch=arch)
    assert cong["channel_width"] == arch.channel_width == 400
    # recompute total HPWL over distinct routed nets from the IR
    dst = np.repeat(np.arange(pir.n_signals), np.diff(pir.fanin_ptr))
    src = pir.fanin_sig
    m = (pir.sig_lb[src] >= 0) & (pir.sig_lb[dst] >= 0) \
        & (pir.sig_lb[src] != pir.sig_lb[dst])
    hx0 = {}
    for s, d in zip(src[m], dst[m]):
        xs = (pir.sig_x[s], pir.sig_x[d])
        ys = (pir.sig_y[s], pir.sig_y[d])
        if s in hx0:
            x0, x1, y0, y1 = hx0[s]
            hx0[s] = (min(x0, *xs), max(x1, *xs), min(y0, *ys), max(y1, *ys))
        else:
            hx0[s] = (min(xs), max(xs), min(ys), max(ys))
    want_v = float(sum(x1 - x0 for x0, x1, _, _ in hx0.values()))
    want_h = float(sum(y1 - y0 for _, _, y0, y1 in hx0.values()))
    assert np.isclose(cong["vertical"].sum(), want_v)
    assert np.isclose(cong["horizontal"].sum(), want_h)
