"""A frozen copy of the program's netlist data model and its plain
functional evaluator (``repro.core.netlist``): the signal, LUT, carry-chain
and bus fields, the topological order, and ``eval_netlist``.

The benchmark's circuits are made by the program's generators; the
reference reads only their plain fields (:func:`from_fields`) and runs no
code of the program on them.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

CONST0 = 0
CONST1 = 1


def tt_eval(tt: int, assignment: int) -> int:
    return (tt >> assignment) & 1



@dataclass
class Chain:
    """A ripple-carry chain of 1-bit full adders.

    Bit ``i`` computes ``sums[i] = a[i] ^ b[i] ^ c_i`` with
    ``c_{i+1} = MAJ(a[i], b[i], c_i)`` and ``c_0 = cin``.
    """

    a: list[int]
    b: list[int]
    sums: list[int]
    cin: int = CONST0
    cout: int | None = None

    def n_adders(self) -> int:
        return len(self.sums)


class Netlist:
    """The data of a netlist: what packing, timing and evaluation read."""

    def __init__(self, name: str = "") -> None:
        self.name = name
        self.n_signals = 2  # const0, const1
        self.pis: list[int] = []
        self.pi_buses: dict[str, list[int]] = {}
        self.pos: dict[str, list[int]] = {}
        self.lut_inputs: list[tuple[int, ...]] = []
        self.lut_tt: list[int] = []
        self.lut_out: list[int] = []
        self.chains: list[Chain] = []
        # signal -> driver: ("pi",idx) ("lut",idx) ("chain",ci,bi) ("cout",ci)
        self.driver: dict[int, tuple] = {}

    @property
    def n_luts(self) -> int:
        return len(self.lut_out)

    @property
    def n_adders(self) -> int:
        return sum(c.n_adders() for c in self.chains)

    # -- topology ------------------------------------------------------------
    def node_list(self) -> list[tuple]:
        """All nodes: ("lut", i) and ("chain", i)."""
        return [("lut", i) for i in range(self.n_luts)] + [
            ("chain", i) for i in range(len(self.chains))
        ]

    def node_inputs(self, node: tuple) -> list[int]:
        kind, idx = node
        if kind == "lut":
            return list(self.lut_inputs[idx])
        ch = self.chains[idx]
        ins = list(ch.a) + list(ch.b)
        if ch.cin not in (CONST0, CONST1):
            ins.append(ch.cin)
        return ins

    def node_outputs(self, node: tuple) -> list[int]:
        kind, idx = node
        if kind == "lut":
            return [self.lut_out[idx]]
        ch = self.chains[idx]
        outs = list(ch.sums)
        if ch.cout is not None:
            outs.append(ch.cout)
        return outs

    def topo_order(self) -> list[tuple]:
        """Kahn topological order over LUT/chain nodes."""
        nodes = self.node_list()
        produced_by: dict[int, tuple] = {}
        for nd in nodes:
            for s in self.node_outputs(nd):
                produced_by[s] = nd
        indeg: dict[tuple, int] = {nd: 0 for nd in nodes}
        consumers: dict[tuple, list[tuple]] = {nd: [] for nd in nodes}
        for nd in nodes:
            deps = set()
            for s in self.node_inputs(nd):
                p = produced_by.get(s)
                if p is not None and p != nd:
                    deps.add(p)
            indeg[nd] = len(deps)
            for p in deps:
                consumers[p].append(nd)
        from collections import deque

        q = deque([nd for nd in nodes if indeg[nd] == 0])
        order = []
        while q:
            nd = q.popleft()
            order.append(nd)
            for c in consumers[nd]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    q.append(c)
        if len(order) != len(nodes):
            raise RuntimeError("combinational cycle in netlist")
        return order



def from_fields(net) -> Netlist:
    """Copy the plain fields of any netlist object (the program's
    ``Netlist`` among them) into the reference's own data model.  The
    driver map is rebuilt from the fields, not copied."""
    ref = Netlist(net.name)
    ref.n_signals = int(net.n_signals)
    ref.pis = [int(s) for s in net.pis]
    ref.pi_buses = {k: [int(s) for s in v] for k, v in net.pi_buses.items()}
    ref.pos = {k: [int(s) for s in v] for k, v in net.pos.items()}
    ref.lut_inputs = [tuple(int(s) for s in ins) for ins in net.lut_inputs]
    ref.lut_tt = [int(t) for t in net.lut_tt]
    ref.lut_out = [int(s) for s in net.lut_out]
    ref.chains = [Chain(a=[int(s) for s in ch.a], b=[int(s) for s in ch.b],
                        sums=[int(s) for s in ch.sums], cin=int(ch.cin),
                        cout=None if ch.cout is None else int(ch.cout))
                  for ch in net.chains]
    for i, s in enumerate(ref.pis):
        ref.driver[s] = ("pi", i)
    for i, s in enumerate(ref.lut_out):
        ref.driver[s] = ("lut", i)
    for ci, ch in enumerate(ref.chains):
        for bi, s in enumerate(ch.sums):
            ref.driver[s] = ("chain", ci, bi)
        if ch.cout is not None:
            ref.driver[ch.cout] = ("cout", ci)
    return ref


def digest(net) -> str:
    """The benchmark's own content digest of a netlist's plain fields
    (signals, buses, LUT pins, truth tables and outputs, chains): what a
    configuration file pins.  Independent of the program's digests."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((
        int(net.n_signals), [int(s) for s in net.pis],
        sorted((k, [int(s) for s in v]) for k, v in net.pi_buses.items()),
        sorted((k, [int(s) for s in v]) for k, v in net.pos.items()),
        [tuple(int(s) for s in ins) for ins in net.lut_inputs],
        [int(t) for t in net.lut_tt], [int(s) for s in net.lut_out],
        [([int(s) for s in c.a], [int(s) for s in c.b],
          [int(s) for s in c.sums], int(c.cin),
          None if c.cout is None else int(c.cout)) for c in net.chains],
    )).encode())
    return h.hexdigest()


def eval_netlist(net: Netlist, pi_values: dict[int, int], n_vectors: int = 1):
    """Evaluate bit-parallel over arbitrary-width python ints.

    ``pi_values[signal] = int`` whose bit ``v`` is the signal's value in test
    vector ``v``.  Returns ``dict signal -> int`` for every signal.
    """
    mask = (1 << n_vectors) - 1
    val: dict[int, int] = {CONST0: 0, CONST1: mask}
    val.update({s: v & mask for s, v in pi_values.items()})
    for nd in net.topo_order():
        kind, idx = nd
        if kind == "lut":
            ins = net.lut_inputs[idx]
            tt = net.lut_tt[idx]
            out = 0
            # sum-of-minterms, bit-parallel
            for m in range(1 << len(ins)):
                if not tt_eval(tt, m):
                    continue
                term = mask
                for j, s in enumerate(ins):
                    sv = val[s]
                    term &= sv if (m >> j) & 1 else (~sv & mask)
                    if term == 0:
                        break
                out |= term
            val[net.lut_out[idx]] = out
        else:
            ch = net.chains[idx]
            c = val[ch.cin]
            for i in range(len(ch.sums)):
                av, bv = val[ch.a[i]], val[ch.b[i]]
                val[ch.sums[i]] = av ^ bv ^ c
                c = (av & bv) | (c & (av ^ bv))
            if ch.cout is not None:
                val[ch.cout] = c
    return val
