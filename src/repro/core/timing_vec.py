"""Vectorized static timing over the unified columnar CircuitIR.

The Python oracle (:func:`repro.core.timing.analyze_oracle`) walks dicts
signal-by-signal; this module executes the same levelized longest-path
recurrence as array programs over :class:`~repro.core.circuit_ir.CircuitIR`
(the same lowering the fused evaluator and the equivalence lanes read):

* **numpy backend** — one gather/max per level, ragged (unpadded) level
  tables, zero compile cost.  This is what ``timing.analyze`` uses for
  one-off pack-and-analyze calls (every figure driver).
* **jax backend** — levels are bucketed into contiguous width segments
  (the evaluator's padded-volume DP), each bucket runs as one
  ``lax.scan``, and the whole suite is batched with a nested ``vmap``:
  outer over circuits (stacked, sink-padded tensors), inner over
  architectures (delay-table rows).  One jit program re-times a whole
  benchmark suite across an N-point arch grid — the engine behind
  :mod:`repro.core.sweep`.

Value identity
--------------
Both backends are **bit-identical** to the oracle (not merely close):
every delay is an integer number of centi-picoseconds
(:meth:`~repro.core.alm.ArchParams.delay_table`), so sums are exact in
any order and ``max`` is exact too — on the host (int64) and on the
device (int32, which a TPU computes natively; :meth:`SuiteTimingProgram.
run` checks the longest formable path fits).  Critical paths leave this
module in centi-picoseconds; :func:`metrics_from_cp` turns them into the
picosecond record.  Padding exploits the model invariant that delays are
non-negative: padded slots gather signal 0 (CONST0, arrival 0) through
the all-zero null edge class and wire tier 0, reproducing the oracle's
``default=0`` reductions exactly.

The *wire* term is the placement-derived inter-LB hop delay: each edge
carries a wire tier (0..3, see ``TIER_*`` in :mod:`repro.core.circuit_ir`)
gathered from a per-arch 4-entry component table.  Unplaced IRs carry
tier 0 everywhere and tier 0's delay is 0, so the placed path at zero
wire-tier delay reproduces the placement-free timing exactly.

Delay tables are data, not structure: an edge stores a *class* (0..26,
see :mod:`repro.core.circuit_ir`), the per-arch component table is built
here by :func:`delay_components` from ``ArchParams.delay_table()`` rows.
Batching across architectures is therefore just a leading axis on the
component tables — no retrace, no repack.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from . import timing as _timing
from .alm import ArchParams, DELAY_FIELDS
from .circuit_ir import (N_EDGE_CLASSES, N_NODE_CLASSES, NDC_ABSORBED,
                         NDC_LUT4, NDC_LUT5, NDC_LUT6, CircuitIR)
from .plan import bucket_envelopes, combined_profile, segment_levels

_IDX = {f: i for i, f in enumerate(DELAY_FIELDS)}

#: jit executables shared across :class:`SuiteTimingProgram` instances,
#: keyed by the full shape signature (signal count, stacked member count,
#: per-bucket flags + tensor shapes, PO width).  The compiled function
#: reads every member-specific value from its *arguments*, so any two
#: programs with equal signatures can share one executable — and with
#: ``pad_shapes=True`` (below) signatures are quantized so that nearby
#: batch compositions actually collide.  Unbounded on purpose: entries
#: are a few compiled closures, not data.
_JIT_CACHE: dict[tuple, object] = {}

#: how many programs were built, how many jit executables that actually
#: compiled vs reused — the serving benchmark records the delta to prove
#: shape padding converts compiles into reuses.
_COMPILE_COUNTS = {"programs": 0, "jit_built": 0, "jit_reused": 0}


def read_compile_counts() -> dict:
    """Snapshot of program-build vs jit-compile/reuse counters."""
    return dict(_COMPILE_COUNTS)


def _pad_dim(n: int, floor: int = 4) -> int:
    """Round ``n`` up to the next power of two, at least ``floor`` —
    the shape quantizer behind ``pad_shapes``."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


def delay_components(tables: np.ndarray) -> dict[str, np.ndarray]:
    """Expand integer delay-table rows ``[..., len(DELAY_FIELDS)]``
    (centi-picoseconds, :meth:`ArchParams.delay_table`) into the
    component tables the executors gather from (leading axes preserved):

    * ``edge [..., 27, 3]`` — (route, pin, path) components per edge class;
    * ``wire [..., 4]``     — inter-LB delay per wire tier (null/tile-local,
      1-hop, 2-hop, long); tier 0 is 0 so unplaced edges (and padding)
      add nothing;
    * ``lut  [..., 4, 3]``  — (lut_delay, t_alm_out, t_out_mux_extra) per
      node delay class (all-zero for absorbed LUTs);
    * ``chain [..., 3]``    — (t_sum_out, t_out_mux_extra, t_carry).
    """
    t = np.asarray(tables)
    if not np.issubdtype(t.dtype, np.integer):
        raise TypeError("delay tables are integer centi-picoseconds "
                        f"(ArchParams.delay_table()), got {t.dtype}")
    t = t.astype(np.int64)
    lead = t.shape[:-1]
    z = np.zeros(lead, dtype=np.int64)

    def g(name):
        return t[..., _IDX[name]]

    route = np.stack([z, g("t_route_local"), g("t_route_global")], axis=-1)
    pin = np.stack([z, g("t_lbin_to_ah"), g("t_lbin_to_z")], axis=-1)
    path = np.stack([z, g("t_ah_to_adder"), g("t_z_to_adder")], axis=-1)
    edge = np.zeros(lead + (N_EDGE_CLASSES, 3), dtype=np.int64)
    for c in range(N_EDGE_CLASSES):
        edge[..., c, 0] = route[..., c // 9]
        edge[..., c, 1] = pin[..., (c // 3) % 3]
        edge[..., c, 2] = path[..., c % 3]

    lut = np.zeros(lead + (N_NODE_CLASSES, 3), dtype=np.int64)
    for ndc, d in ((NDC_LUT4, g("t_lut4")), (NDC_LUT5, g("t_lut5")),
                   (NDC_LUT6, g("t_lut6"))):
        lut[..., ndc, 0] = d
        lut[..., ndc, 1] = g("t_alm_out")
        lut[..., ndc, 2] = g("t_out_mux_extra")
    assert NDC_ABSORBED == 0  # row 0 stays all-zero: absorption adds nothing

    chain = np.stack([g("t_sum_out"), g("t_out_mux_extra"), g("t_carry")],
                     axis=-1)
    wire = np.stack([z, g("t_wire_hop1"), g("t_wire_hop2"),
                     g("t_wire_long")], axis=-1)
    return {"edge": edge, "wire": wire, "lut": lut, "chain": chain}


# ---------------------------------------------------------------------------
# numpy backend (per-circuit, compile-free)
# ---------------------------------------------------------------------------


def arrival_times_numpy(ir: CircuitIR, comps: dict[str, np.ndarray]
                        ) -> np.ndarray:
    """Arrival time per signal, int64 centi-ps, oracle-identical."""
    edge, wire, lutc = comps["edge"], comps["wire"], comps["lut"]
    t_sum, t_extra, t_carry = (int(comps["chain"][0]),
                               int(comps["chain"][1]),
                               int(comps["chain"][2]))
    arr = np.zeros(ir.n_signals, dtype=np.int64)
    for ll, cl in zip(ir.lut_levels, ir.chain_levels):
        if ll.out.shape[0]:
            ec = edge[ll.cls]                          # [M, 6, 3]
            t = (((arr[ll.ins] + ec[..., 0]) + wire[ll.hop])
                 + ec[..., 1]) + ec[..., 2]
            tin = t.max(axis=1)
            nc = lutc[ll.ndc]                          # [M, 3]
            arr[ll.out] = ((tin + nc[:, 0]) + nc[:, 1]) + nc[:, 2]
        C = cl.cout.shape[0]
        if C:
            ea, eb = edge[cl.a_cls], edge[cl.b_cls]
            a_t = (((arr[cl.a_sig] + ea[..., 0]) + wire[cl.a_hop])
                   + ea[..., 1]) + ea[..., 2]
            b_t = (((arr[cl.b_sig] + eb[..., 0]) + wire[cl.b_hop])
                   + eb[..., 1]) + eb[..., 2]
            ecin = edge[cl.cin_cls]
            c = (((arr[cl.cin_sig] + ecin[:, 0]) + wire[cl.cin_hop])
                 + ecin[:, 1]) + ecin[:, 2]
            B = cl.a_sig.shape[1]
            carries = np.zeros((C, B), dtype=np.int64)
            for bi in range(B):
                th = np.maximum(np.maximum(a_t[:, bi], b_t[:, bi]), c)
                valid = cl.sums[:, bi] >= 0
                if valid.any():
                    arr[cl.sums[valid, bi]] = (th[valid] + t_sum) + t_extra
                c = th + t_carry
                carries[:, bi] = c
            has = cl.cout >= 0
            if has.any():
                cy = carries[np.flatnonzero(has), cl.last[has]]
                arr[cl.cout[has]] = (cy + t_sum) + t_extra
    return arr


def critical_path_numpy(ir: CircuitIR, comps: dict[str, np.ndarray]) -> int:
    """Latest primary-output arrival, centi-ps (0 without outputs)."""
    arr = arrival_times_numpy(ir, comps)
    return int(arr[ir.po_sig].max()) if ir.po_sig.size else 0


def metrics_from_cp(ir: CircuitIR, arch: ArchParams, cp_cps: int) -> dict:
    """The :func:`repro.core.timing.analyze` record for one (IR, arch,
    critical path in centi-ps).

    ``n_alms``/``n_lbs``/``concurrent_luts`` come from the IR (structure);
    area comes from the arch row — within a structural class only the
    area constant and the delays differ, which is why one IR serves every
    grid row of its class."""
    return _timing.metrics_from_cp(
        arch, cp_cps, ir.n_alms, ir.n_lbs, ir.n_alms * arch.alm_area_mwta,
        ir.n_adders, ir.n_luts, ir.concurrent_luts)


def analyze_ir(ir: CircuitIR, arch: ArchParams, backend: str = "numpy") -> dict:
    """Vectorized :func:`repro.core.timing.analyze` over a lowered pack."""
    if backend == "numpy":
        comps = delay_components(arch.delay_table())
        cp = critical_path_numpy(ir, comps)
    elif backend == "jax":
        prog = build_suite_timing_program([ir])
        cp = int(prog.run(arch.delay_table()[None, :])[0, 0])
    else:
        raise ValueError(f"unknown timing backend {backend!r}")
    return metrics_from_cp(ir, arch, cp)


# ---------------------------------------------------------------------------
# jax backend (suite x arch-grid batched program)
# ---------------------------------------------------------------------------


def _alloc_bucket(l: int, M1: int, C1: int, B1: int, sink: int):
    """One bucket's all-pad (null) 17-tuple: every gather reads signal 0
    (CONST0, arrival 0) through edge class 0 / wire tier 0, every
    scatter lands on ``sink`` — a no-op level row."""
    return (np.zeros((l, M1, 6), dtype=np.int32),       # l_ins
            np.zeros((l, M1, 6), dtype=np.int32),       # l_cls
            np.zeros((l, M1), dtype=np.int32),          # l_ndc
            np.full((l, M1), sink, dtype=np.int32),     # l_out
            np.zeros((l, C1, B1), dtype=np.int32),      # a_sig
            np.zeros((l, C1, B1), dtype=np.int32),      # a_cls
            np.zeros((l, C1, B1), dtype=np.int32),      # b_sig
            np.zeros((l, C1, B1), dtype=np.int32),      # b_cls
            np.zeros((l, C1), dtype=np.int32),          # cin_sig
            np.zeros((l, C1), dtype=np.int32),          # cin_cls
            np.full((l, C1, B1), sink, dtype=np.int32),  # sums
            np.full((l, C1), sink, dtype=np.int32),     # cout
            np.zeros((l, C1), dtype=np.int32),          # last
            np.zeros((l, M1, 6), dtype=np.int32),       # l_hop
            np.zeros((l, C1, B1), dtype=np.int32),      # a_hop
            np.zeros((l, C1, B1), dtype=np.int32),      # b_hop
            np.zeros((l, C1), dtype=np.int32))          # cin_hop


def _pad_levels(ir: CircuitIR, bounds, shapes, sink: int):
    """Pad one member's ragged level tables to the bucketed group envelope
    (``shapes[bi] = (l, M1, C1, B1)``, possibly quantized upward);
    returns per-bucket 17-tuples of [l, ...] arrays (the scan xs).  The
    wire-tier (hop) arrays ride at indices 13..16 so the flag probes on
    indices 3/10/11 stay valid; padded slots keep tier 0 (zero delay)."""
    out = []
    for (i, j), (l, M1, C1, B1) in zip(bounds, shapes):
        (l_ins, l_cls, l_ndc, l_out, a_sig, a_cls, b_sig, b_cls,
         cin_sig, cin_cls, sums, cout, last,
         l_hop, a_hop, b_hop, cin_hop) = _alloc_bucket(l, M1, C1, B1, sink)
        for t in range(i, min(j, ir.n_levels)):
            r = t - i
            ll, cl = ir.lut_levels[t], ir.chain_levels[t]
            m = ll.out.shape[0]
            if m:
                l_ins[r, :m] = ll.ins
                l_cls[r, :m] = ll.cls
                l_hop[r, :m] = ll.hop
                l_ndc[r, :m] = ll.ndc
                l_out[r, :m] = ll.out
            c = cl.cout.shape[0]
            if c:
                bb = cl.a_sig.shape[1]
                a_sig[r, :c, :bb] = cl.a_sig
                a_cls[r, :c, :bb] = cl.a_cls
                a_hop[r, :c, :bb] = cl.a_hop
                b_sig[r, :c, :bb] = cl.b_sig
                b_cls[r, :c, :bb] = cl.b_cls
                b_hop[r, :c, :bb] = cl.b_hop
                cin_sig[r, :c] = cl.cin_sig
                cin_cls[r, :c] = cl.cin_cls
                cin_hop[r, :c] = cl.cin_hop
                s = cl.sums.copy()
                s[s < 0] = sink
                sums[r, :c, :bb] = s
                co = cl.cout.copy()
                co[co < 0] = sink
                cout[r, :c] = co
                last[r, :c] = cl.last
        out.append((l_ins, l_cls, l_ndc, l_out, a_sig, a_cls, b_sig, b_cls,
                    cin_sig, cin_cls, sums, cout, last,
                    l_hop, a_hop, b_hop, cin_hop))
    return out


@dataclass
class SuiteTimingProgram:
    """One batched timing program: G stacked circuits x K delay rows.

    ``run(delay_tables[K, len(DELAY_FIELDS)])`` returns critical paths
    ``[G, K]`` (int64 centi-ps), bit-identical to the oracle per
    (circuit, arch).
    The program is jit-compiled once per (shape, K); re-running with new
    delay rows of the same K reuses the compile — an arch-grid sweep is
    pure data motion after the first call.
    """

    n_sig: int
    n_members: int
    flags: tuple[tuple[bool, bool], ...]
    bucket_shapes: tuple[tuple[int, int, int, int], ...]
    _tensors: tuple = field(repr=False)
    _po: object = field(repr=False)
    _jit: object = field(default=None, repr=False)
    #: full compiled-shape signature; programs with equal signatures
    #: share one jit executable through the module ``_JIT_CACHE``
    shape_key: tuple | None = None

    def _build_jit(self):
        import functools

        import jax
        import jax.numpy as jnp

        flags = self.flags
        n_sig = self.n_sig

        def body(arr, xs, *, hl, hc, edge, wire, lutc, chainc):
            (l_ins, l_cls, l_ndc, l_out, a_sig, a_cls, b_sig, b_cls,
             cin_sig, cin_cls, sums, cout, last,
             l_hop, a_hop, b_hop, cin_hop) = xs
            if hl:
                ec = edge[l_cls]
                t = (((arr[l_ins] + ec[..., 0]) + wire[l_hop])
                     + ec[..., 1]) + ec[..., 2]
                tin = jnp.max(t, axis=1)
                nc = lutc[l_ndc]
                arr = arr.at[l_out].set(
                    ((tin + nc[:, 0]) + nc[:, 1]) + nc[:, 2])
            if hc:
                ea, eb = edge[a_cls], edge[b_cls]
                a_t = (((arr[a_sig] + ea[..., 0]) + wire[a_hop])
                       + ea[..., 1]) + ea[..., 2]
                b_t = (((arr[b_sig] + eb[..., 0]) + wire[b_hop])
                       + eb[..., 1]) + eb[..., 2]
                ecin = edge[cin_cls]
                c0 = (((arr[cin_sig] + ecin[:, 0]) + wire[cin_hop])
                      + ecin[:, 1]) + ecin[:, 2]
                t_sum, t_extra, t_carry = chainc[0], chainc[1], chainc[2]

                def ripple(c, ab):
                    at, bt = ab
                    th = jnp.maximum(jnp.maximum(at, bt), c)
                    cy = th + t_carry
                    return cy, (th, cy)

                _, (ths, cys) = jax.lax.scan(
                    ripple, c0, (a_t.swapaxes(0, 1), b_t.swapaxes(0, 1)))
                arr = arr.at[sums].set((ths.swapaxes(0, 1) + t_sum) + t_extra)
                cy_last = jnp.take_along_axis(
                    cys.swapaxes(0, 1), last[:, None], axis=1)[:, 0]
                arr = arr.at[cout].set((cy_last + t_sum) + t_extra)
            return arr, None

        def one(member_xs, po, edge, wire, lutc, chainc):
            arr = jnp.zeros(n_sig + 1, dtype=jnp.int32)
            for (hl, hc), xs in zip(flags, member_xs):
                bk = functools.partial(body, hl=hl, hc=hc, edge=edge,
                                       wire=wire, lutc=lutc, chainc=chainc)
                arr, _ = jax.lax.scan(bk, arr, xs)
            return jnp.max(arr[po])

        inner = jax.vmap(one, in_axes=(None, None, 0, 0, 0, 0))  # arch axis
        outer = jax.vmap(inner, in_axes=(0, 0, None, None, None, None))
        return jax.jit(outer)

    def run(self, delay_tables: np.ndarray) -> np.ndarray:
        """Critical paths ``[G, K]`` (centi-ps) for integer delay rows
        ``[K, |DELAY_FIELDS|]``."""
        comps = delay_components(delay_tables)
        # arrivals are int32 on the device.  Each level adds at most one
        # edge and either one LUT node or one full carry ripple, so this
        # bounds every arrival the program can form.
        levels = sum(l for l, _, _, _ in self.bucket_shapes)
        bits = max(B for _, _, _, B in self.bucket_shapes)
        chain = comps["chain"].reshape(-1, 3)
        step = (comps["edge"].sum(-1).max() + comps["wire"].max()
                + max(comps["lut"].sum(-1).max(),
                      (bits * chain[:, 2] + chain[:, 0] + chain[:, 1]).max()))
        if levels * int(step) >= 2 ** 31:
            raise OverflowError(
                f"timing program: {levels} levels x {int(step)} cps per "
                f"level may overflow int32 arrivals")
        if self._jit is None:
            jit = (_JIT_CACHE.get(self.shape_key)
                   if self.shape_key is not None else None)
            if jit is None:
                jit = self._build_jit()
                _COMPILE_COUNTS["jit_built"] += 1
                if self.shape_key is not None:
                    _JIT_CACHE[self.shape_key] = jit
            else:
                _COMPILE_COUNTS["jit_reused"] += 1
            self._jit = jit
        cps = self._jit(self._tensors, self._po,
                        *(comps[k].astype(np.int32)
                          for k in ("edge", "wire", "lut", "chain")))
        # rows past n_members are pad members, sliced away
        return np.asarray(cps, dtype=np.int64)[:self.n_members]


def build_suite_timing_program(irs: Sequence[CircuitIR],
                               max_buckets: int = 3,
                               pad_shapes: bool = False
                               ) -> SuiteTimingProgram:
    """Stack many circuits' CircuitIRs into one width-bucketed timing program.

    Levels are aligned to the longest member, the combined width profile
    is segmented by the evaluator's padded-volume DP, and every member is
    padded to the bucket envelopes with null rows (sink-scattering,
    zero-gathering).  One program serves the whole suite.

    ``pad_shapes=True`` additionally quantizes every compiled dimension
    (signal space, member count, PO width, per-bucket level count and
    envelope) up to the next power of two, so *different* batch
    compositions land on the same shape signature and share one jit
    executable through the module ``_JIT_CACHE`` — the flow server's
    edit streams and rotating tenant batches stop recompiling per batch.
    Padding is value-neutral by the model invariant documented in the
    module docstring: pad slots gather CONST0 through the all-zero null
    edge class, pad members scatter only to the sink and are sliced off
    by :meth:`SuiteTimingProgram.run`."""
    import jax.numpy as jnp

    if not irs:
        raise ValueError("empty IR list")
    L = max(ir.n_levels for ir in irs)
    m, c, b = combined_profile([ir.level_profile() for ir in irs], L)
    L = max(L, 1)
    bounds = segment_levels(m, c, b, max_buckets)
    envelopes = bucket_envelopes(m, c, b, bounds)
    n_sig = max(ir.n_signals for ir in irs)
    G = len(irs)
    G_alloc = G
    P = max(max((ir.po_sig.size for ir in irs), default=1), 1)
    shapes = [(max(j - i, 1), max(M, 1), max(C, 1), max(B, 1))
              for (i, j), (M, C, B) in zip(bounds, envelopes)]
    if pad_shapes:
        n_sig = _pad_dim(n_sig, floor=64)
        G_alloc = _pad_dim(G, floor=1)
        P = _pad_dim(P, floor=4)
        shapes = [(_pad_dim(l), _pad_dim(M1), _pad_dim(C1, floor=1),
                   _pad_dim(B1, floor=1)) for l, M1, C1, B1 in shapes]
    sink = n_sig
    members = [_pad_levels(ir, bounds, shapes, sink) for ir in irs]
    members += [[_alloc_bucket(*s, sink) for s in shapes]
                ] * (G_alloc - G)                           # pad members
    tensors = tuple(
        tuple(jnp.asarray(np.stack([mb[bi][ai] for mb in members]))
              for ai in range(17))
        for bi in range(len(bounds)))
    po = np.zeros((G_alloc, P), dtype=np.int32)    # pad -> CONST0 (arr 0)
    for g, ir in enumerate(irs):
        po[g, :ir.po_sig.size] = ir.po_sig
    flags = tuple(
        (any(mb[bi][3].min() < sink for mb in members[:G]),  # any real lut out
         any(mb[bi][11].min() < sink or (mb[bi][10] < sink).any()
             for mb in members[:G]))                         # any real chain
        for bi in range(len(bounds)))
    _COMPILE_COUNTS["programs"] += 1
    return SuiteTimingProgram(
        n_sig=n_sig, n_members=G, flags=flags, bucket_shapes=tuple(shapes),
        _tensors=tensors, _po=jnp.asarray(po),
        shape_key=(n_sig, G_alloc, P, flags, tuple(shapes)))
