"""Timing-model behaviour: Table II path relationships must show up."""
from repro.core.alm import BASELINE, DD5, DD6
from repro.core.circuits import kratos_gemm, sha_like, vtr_mixed
from repro.core.netlist import Netlist
from repro.core.packing import pack
from repro.core.timing import analyze, channel_utilization


def test_dd6_slower_than_dd5():
    net = kratos_gemm(m=6, n=6, width=6, sparsity=0.5)
    r5 = analyze(pack(net, DD5, seed=0))
    r6 = analyze(pack(net, DD6, seed=0))
    assert r6["critical_path_ps"] > r5["critical_path_ps"]


def test_z_path_speeds_up_adder_chains():
    """A pure adder circuit: DD5 feeds raw operands through Z (68.77 ps)
    instead of the LUT route (133.4 ps) -> lower critical path."""
    net = Netlist("adders")
    a = net.add_pi_bus("a", 32)
    b = net.add_pi_bus("b", 32)
    sums, _ = net.add_chain(list(a), list(b))
    net.set_po_bus("s", sums)
    r0 = analyze(pack(net, BASELINE, seed=0))
    r5 = analyze(pack(net, DD5, seed=0))
    assert r5["critical_path_ps"] < r0["critical_path_ps"]


def test_delay_roughly_flat_dd5():
    """Paper Fig. 6: average critical path is at the baseline level
    (within a few percent either way)."""
    for mk in (lambda: kratos_gemm(m=6, n=6, width=6, sparsity=0.5),
               lambda: vtr_mixed(logic_nodes=200, adders=3),
               lambda: sha_like(rounds=1)):
        net = mk()
        r0 = analyze(pack(net, BASELINE, seed=0))
        r5 = analyze(pack(net, DD5, seed=0))
        ratio = r5["critical_path_ps"] / r0["critical_path_ps"]
        assert 0.85 < ratio < 1.16, (net.name, ratio)


def test_area_model_tile_constants():
    assert abs(DD5.alm_area_mwta / BASELINE.alm_area_mwta - 1.0372) < 1e-6
    assert DD6.alm_area_mwta > DD5.alm_area_mwta


def test_channel_utilization_shifts_up_dd5():
    """Fig. 8: same logic in fewer LBs -> higher per-LB routing demand."""
    net = kratos_gemm(m=8, n=8, width=6, sparsity=0.5)
    u0 = channel_utilization(pack(net, BASELINE, seed=0))
    u5 = channel_utilization(pack(net, DD5, seed=0))
    assert sum(u5) / len(u5) > sum(u0) / len(u0)


def test_fmax_in_plausible_range():
    """Table III: suite Fmax averages sit around 70-160 MHz."""
    net = kratos_gemm(m=8, n=8, width=6, sparsity=0.5)
    r = analyze(pack(net, BASELINE, seed=0))
    assert 40 < r["fmax_mhz"] < 400


def test_delay_table_is_whole_centi_picoseconds():
    """Timing computes in integer centi-ps: the delay table holds them
    exactly, and a delay finer than 0.01 ps is refused, not rounded."""
    import numpy as np
    import pytest

    from repro.core.alm import DELAY_FIELDS, make_arch

    table = DD5.delay_table()
    assert table.dtype == np.int64
    row = dict(zip(DELAY_FIELDS, table.tolist()))
    assert row["t_ah_to_adder"] == 20220 and row["t_lbin_to_ah"] == 7261
    fan5 = make_arch("f5", bypass_inputs=2, addmux_fanin=5)  # 77.05 - 4.5
    assert dict(zip(DELAY_FIELDS, fan5.delay_table()))["t_lbin_to_z"] == 7255
    with pytest.raises(ValueError, match="0.01 ps"):
        make_arch("fine", t_wire_hop1=25.001).delay_table()
