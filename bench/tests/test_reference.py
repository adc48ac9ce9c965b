"""The plain references under ``bench/reference`` are frozen copies of the
program's packer, timing oracle and evaluator: on the same circuits they
give the program's records and outputs exactly, and their controls (float32
timing, 16-bit lane words) give something else."""
import random

import numpy as np
import pytest

from bench.reference import alm as ref_alm
from bench.reference import lanes as ref_lanes
from bench.reference import netlist as ref_netlist
from bench.reference import packing as ref_packing
from bench.reference import timing as ref_timing

RECORD = ("critical_path_ps", "area_mwta", "alms", "lbs", "adp",
          "concurrent_luts")


@pytest.fixture(scope="module")
def nets():
    from repro.core.circuits import koios_mac_array, sha_like, vtr_mixed

    return [vtr_mixed("logic", n_in=16, logic_nodes=120, adders=3,
                      add_width=10, seed=3),
            koios_mac_array("mac", pes=2, width=4, ctrl_nodes=40, seed=1),
            sha_like("sha", rounds=1, width=8)]


@pytest.mark.parametrize("arch", ["baseline", "dd5", "dd6", "b1_f8"])
def test_pack_and_timing_equal_the_program(nets, arch):
    from repro.core.alm import ARCHS, make_arch
    from repro.core.packing import pack
    from repro.core.timing import analyze_oracle

    if arch == "b1_f8":
        prog = make_arch(arch, bypass_inputs=1, addmux_fanin=8)
        ref = ref_alm.make_arch(arch, bypass_inputs=1, addmux_fanin=8)
    else:
        prog, ref = ARCHS[arch], ref_alm.ARCHS[arch]
    for net in nets:
        want = analyze_oracle(pack(net, prog, seed=7))
        got = ref_timing.analyze_oracle(
            ref_packing.pack(ref_netlist.from_fields(net), ref, seed=7))
        assert {k: got[k] for k in RECORD} == {k: want[k] for k in RECORD}


def test_arch_grid_equals_the_program():
    from repro.core.alm import full_arch_grid

    prog, ref = full_arch_grid(), ref_alm.full_arch_grid()
    assert [a.name for a in prog] == [a.name for a in ref]
    for a, b in zip(prog, ref):
        assert a.structural_key() == b.structural_key()
        assert a.alm_area_mwta == b.alm_area_mwta
        assert np.array_equal(a.delay_table(), b.delay_table())


def test_eval_equals_the_program(nets):
    from repro.core.netlist import eval_netlist

    rng = random.Random(5)
    for net in nets:
        pis = {s: rng.getrandbits(96) for s in net.pis}
        ref = ref_netlist.eval_netlist(ref_netlist.from_fields(net), pis, 96)
        assert ref == eval_netlist(net, pis, 96)


def test_lanes_match_the_program_draw(nets):
    from repro.core.flow import random_lanes

    for net in nets:
        a = random_lanes(net, 3, seed=11)
        b = ref_lanes.seeded_lanes(net, 3, seed=11)
        assert all(np.array_equal(a[s], b[s]) for s in net.pis)


def test_timing_control_is_not_exact(nets):
    """float32 picoseconds, the step below integer centi-picoseconds, do
    not reproduce the exact records."""
    bad = 0
    for net in nets:
        for arch in ref_alm.ARCHS.values():
            p = ref_packing.pack(ref_netlist.from_fields(net), arch, seed=0)
            exact = ref_timing.analyze_oracle(p)
            ctrl = ref_timing.analyze_oracle(p, dtype=np.float32)
            bad += exact["critical_path_ps"] != ctrl["critical_path_ps"]
    assert bad > 0


def test_eval_control_is_not_exact(nets):
    """Evaluation on 16-bit lane words loses the upper vectors."""
    for net in nets:
        ref = ref_netlist.from_fields(net)
        lanes = ref_lanes.seeded_lanes(ref, 2, seed=3)
        exact = ref_lanes.reference_pos(ref, lanes)
        ctrl = ref_lanes.reference_pos(ref, lanes, half_words=True)
        assert ref_lanes.count_mismatches(ctrl, exact) > 0
        assert ref_lanes.count_mismatches(exact, exact) == 0


def test_digest_ignores_the_name_and_sees_every_field(nets):
    from repro.core.edits import clone_netlist, edit_lut_tt

    net = nets[0]
    d = ref_netlist.digest(net)
    same = clone_netlist(net)
    same.name = "other"
    assert ref_netlist.digest(same) == d
    edited = clone_netlist(net)
    edit_lut_tt(edited, 0, edited.lut_tt[0] ^ 1)
    assert ref_netlist.digest(edited) != d
