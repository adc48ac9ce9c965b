"""Registers over the frozen netlist fields (:mod:`bench.reference.netlist`):
their plain fields, a digest of them, and gate-level stepping of a
registered netlist over clock cycles with the frozen ``eval_netlist``.

A register is a ``(d, q)`` pair of signals on one clock, reset value 0;
its Q is a free input of the logic between clock edges and its D loads at
the edge that ends a cycle.  Nothing here imports the program.
"""
from __future__ import annotations

import hashlib

from .netlist import eval_netlist, from_fields


def reg_fields(net) -> tuple[list[tuple[int, int]], dict[str, list[int]]]:
    """The registers of any netlist object: ``(d, q)`` pairs and the
    named buses of their Q signals."""
    regs = [(int(d), int(q)) for d, q in net.regs]
    buses = {k: [int(s) for s in v] for k, v in net.reg_buses.items()}
    return regs, buses


def reg_digest(net) -> str:
    """The benchmark's digest of a netlist's registers, pinned beside
    :func:`bench.reference.netlist.digest` (which reads no register)."""
    regs, buses = reg_fields(net)
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((regs, sorted(buses.items()))).encode())
    return h.hexdigest()


def step(net, pi_streams: dict, n_cycles: int, n_vectors: int
         ) -> list[dict[int, int]]:
    """Every primary output's value in each of ``n_cycles`` cycles.

    ``pi_streams[signal][t]`` is a primary input's value in cycle ``t``
    (an int, bit ``v`` for vector ``v``); every register starts at 0."""
    ref = from_fields(net)
    regs, _ = reg_fields(net)
    po = [s for bus in ref.pos.values() for s in bus]
    state = {q: 0 for _, q in regs}
    out = []
    for t in range(n_cycles):
        ins = {s: v[t] for s, v in pi_streams.items()}
        ins.update(state)
        val = eval_netlist(ref, ins, n_vectors)
        out.append({s: val.get(s, 0) for s in po})
        state = {q: val.get(d, 0) for d, q in regs}
    return out
