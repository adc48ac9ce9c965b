"""A frozen copy of the program's timing oracle (``repro.core.timing``:
``analyze_oracle`` and ``metrics_from_cp``): the per-signal Python walk
over a packed circuit, in integer centi-picoseconds, that every critical
path the benchmark checks is compared with.
"""
from __future__ import annotations

from .alm import CPS_PER_PS, DELAY_FIELDS, ArchParams
from .netlist import CONST0, CONST1
from .packing import PackedCircuit


def metrics_from_cp(arch: ArchParams, cp_cps: int, alms: int, lbs: int,
                    area: float, adders: int, luts: int,
                    concurrent_luts: int) -> dict:
    """The timing + area record; the one place centi-picoseconds become
    picoseconds (a path shorter than 1 ps reports 1 ps)."""
    cp = max(int(cp_cps), CPS_PER_PS) / CPS_PER_PS
    return {
        "arch": arch.name,
        "critical_path_ps": cp,
        "fmax_mhz": 1e6 / cp,
        "alms": alms,
        "lbs": lbs,
        "area_mwta": area,
        "adp": area * cp,
        "adders": adders,
        "luts": luts,
        "concurrent_luts": concurrent_luts,
    }


def analyze_oracle(packed: PackedCircuit, placement=None,
                   dtype=None) -> dict:
    """The critical path and area record of ``packed``, in integer
    centi-picoseconds.  ``dtype`` (e.g. ``numpy.float32``) is the
    benchmark's control: the same walk in picoseconds of that float
    type, the step below exact integer timing."""
    net = packed.net
    arch = packed.arch
    d = dict(zip(DELAY_FIELDS, arch.delay_table().tolist()))   # cps
    if dtype is not None:
        d = {k: dtype(v) / dtype(CPS_PER_PS) for k, v in d.items()}

    # production site (alm index) per signal; PIs -> -1
    site: dict[int, int] = {}
    for s in net.pis:
        site[s] = -1
    for li, out in enumerate(net.lut_out):
        ai = packed.lut_site.get(li, -2)
        site[out] = ai
    for ci, ch in enumerate(net.chains):
        for bi, s in enumerate(ch.sums):
            site[s] = packed.chain_site.get((ci, bi), -2)
        if ch.cout is not None:
            site[ch.cout] = packed.chain_site.get((ci, len(ch.sums) - 1), -2)

    def lb_of(ai: int) -> int:
        if ai < 0:
            return -1
        return packed.alm_lb[ai]

    arr: dict[int, int] = {CONST0: 0, CONST1: 0}
    for s in net.pis:
        arr[s] = 0

    def edge_in(s: int, dst_lb: int, pin: str) -> int:
        """Arrival of signal s at an ALM input pin in LB dst_lb."""
        t = arr[s]
        src_lb = lb_of(site.get(s, -1))
        if s <= CONST1:
            return 0
        if src_lb == dst_lb and src_lb >= 0:
            t += d["t_route_local"]
        else:
            t += d["t_route_global"]
            if placement is not None and src_lb >= 0 and dst_lb >= 0:
                hops = (abs(int(placement.lb_x[src_lb])
                            - int(placement.lb_x[dst_lb]))
                        + abs(int(placement.lb_y[src_lb])
                              - int(placement.lb_y[dst_lb])))
                t += (d["t_wire_hop1"] if hops <= 1 else
                      d["t_wire_hop2"] if hops == 2 else d["t_wire_long"])
        t += d["t_lbin_to_z"] if pin == "z" else d["t_lbin_to_ah"]
        return t

    # map (chain,bit) -> half for feed info
    feed: dict[tuple[int, int], tuple[str, list[int]]] = {}
    absorbed_all: set[int] = set()
    for alm in packed.alms:
        for h in alm.halves:
            if h.fa is not None:
                feed[h.fa] = (h.fa_feed, h.absorbed)
                absorbed_all.update(h.absorbed)

    out_extra = d["t_out_mux_extra"]

    for nd in net.topo_order():
        kind, idx = nd
        if kind == "lut":
            out = net.lut_out[idx]
            ai = packed.lut_site.get(idx)
            if ai is None:
                # absorbed LUT timing handled at chain; skip (arr set there)
                continue
            dst_lb = lb_of(ai)
            k = len(net.lut_inputs[idx])
            t_in = max((edge_in(s, dst_lb, "ah") for s in net.lut_inputs[idx]
                        if s > CONST1), default=0)
            # absorbed LUTs have their delay folded into t_ah_to_adder
            if idx in absorbed_all:
                arr[out] = t_in
            else:
                t_lut = (d["t_lut4"] if k <= 4 else
                         d["t_lut5"] if k == 5 else d["t_lut6"])
                arr[out] = t_in + t_lut + d["t_alm_out"] + out_extra
        else:
            ch = net.chains[idx]
            carry = 0
            if ch.cin > CONST1:
                ai0 = packed.chain_site.get((idx, 0), -2)
                carry = edge_in(ch.cin, lb_of(ai0), "ah") + d["t_ah_to_adder"]
            for bi in range(len(ch.sums)):
                ai = packed.chain_site.get((idx, bi), -2)
                dst_lb = lb_of(ai)
                fkind, absorbed = feed.get((idx, bi), ("lut", []))
                ops = [ch.a[bi], ch.b[bi]]
                t_op = 0
                absorbed_outs = {net.lut_out[li] for li in absorbed}
                for s in ops:
                    if s <= CONST1:
                        continue
                    if s in absorbed_outs:
                        # operand computed in the half's own LUTs
                        li = next(l for l in absorbed if net.lut_out[l] == s)
                        tin = max((edge_in(q, dst_lb, "ah")
                                   for q in net.lut_inputs[li] if q > CONST1),
                                  default=0)
                        t_op = max(t_op, tin + d["t_ah_to_adder"])
                    elif fkind == "z":
                        t_op = max(t_op, edge_in(s, dst_lb, "z")
                                   + d["t_z_to_adder"])
                    else:
                        t_op = max(t_op, edge_in(s, dst_lb, "ah")
                                   + d["t_ah_to_adder"])
                t_here = max(t_op, carry)
                arr[ch.sums[bi]] = t_here + d["t_sum_out"] + out_extra
                carry = t_here + d["t_carry"]
            if ch.cout is not None:
                arr[ch.cout] = carry + d["t_sum_out"] + out_extra

    # absorbed luts that never got arr (dangling) -> 0
    cp = 0
    for bus in net.pos.values():
        for s in bus:
            cp = max(cp, arr.get(s, 0))
    rec = metrics_from_cp(arch, cp if dtype is None else 0, packed.n_alms,
                          packed.n_lbs, packed.total_area, net.n_adders,
                          net.n_luts, packed.concurrent_luts)
    if dtype is not None:
        cp_ps = float(cp)
        rec.update(critical_path_ps=cp_ps, fmax_mhz=1e6 / cp_ps,
                   adp=rec["area_mwta"] * cp_ps)
    return rec
