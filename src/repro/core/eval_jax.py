"""Bit-parallel netlist evaluation in JAX (the simulator's compute layer).

Width-bucketed multi-scan engine
--------------------------------
The netlist is lowered once (per content digest) to the unified columnar
:class:`~repro.core.circuit_ir.CircuitIR` — the same functional lowering
that feeds the timing stack and the equivalence lanes — and compiled here
into a :class:`FusedPlan`.  Instead of padding every level to one
worst-case ``[L, M_max, 6]`` envelope (a circuit with one wide level then
wastes rows on every other level), the level sequence is partitioned into
at most ``max_buckets`` *contiguous* segments — width buckets — by the
shared padded-volume DP (:func:`repro.core.plan.segment_levels`).  Each
bucket is padded only to its own envelope ``[l_b, M_b, 6]`` /
``[l_b, C_b, B_b]`` and evaluated by its own ``lax.scan``; the scans run
back-to-back inside a **single jit**, so the one-program property of the
fused engine is preserved while the padding waste drops to the per-bucket
optimum:

* a scan step gathers the level's LUT input lanes from the signal-value
  buffer, runs one fused ``lut_eval6`` kernel call, and writes the
  outputs back: by scatter to each signal's row in the combinational
  programs, whose buffer is returned by signal id, and by one slice into
  the level's own block in the cycle program, whose row numbering is
  private (:class:`SlotPlan`);
* the level's carry chains ripple inside the same scan step (a nested
  bit-scan over the stacked ``[C_b, B_b]`` layout — one scan for *all*
  chains of the level);
* padded rows read constant-0 lanes and write a reserved sink row (or
  their own rows of the level's block), so each scan body is
  shape-uniform with zero per-level Python dispatch.

Suite-scale batched evaluation
------------------------------
Every combinational evaluation runs one kind of device program,
:func:`_run_fused_batch`, prepared by :func:`prepare_suite_program` and
run by :meth:`SuiteProgram.run`; a single circuit is a suite of one
(:func:`eval_netlist_jax`).  Plans are clustered by *compatible envelopes*
(:func:`repro.core.plan.group_by_envelope` — agglomerative merging on the
padded plan volume plus a signal-count term), capped at ``max_groups``
groups, so a whole benchmark suite compiles into a handful of vmapped jit
programs.  Within a group the bucket boundaries are recomputed on the
group's combined per-level width profile, members are padded to the group
envelope, and one ``vmap``-ed multi-scan evaluates the group.

Plans and grouped device tensors are cached by netlist content digest in
the shared registry (:mod:`repro.core.plan` — ``eval_plans`` /
``eval_groups``), alongside the functional IRs; one
:func:`repro.core.plan.clear_caches` invalidates everything.

Registered netlists are simulated over clock cycles by :func:`_run_seq`:
one ``lax.scan`` over cycles whose step is the same fused multi-scan on
a renumbered value buffer (:func:`slot_plan`), with the buffer and the
registers' rows as the carry (:func:`run_sequential`).

The value buffer is built on the device (:func:`_device_vals`): only the
primary inputs' lanes cross the host link, and the buffer is donated to
the evaluation jit (``donate_argnums``), which reuses it in place.  The
Python ``eval_netlist`` oracle in ``netlist.py`` stays the ground truth
in tests.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from . import plan as _planner
from .circuit_ir import CircuitIR, lower_netlist_ir
from .netlist import CONST0, CONST1, Netlist
from .plan import segment_levels
from .spans import span

DEFAULT_MAX_BUCKETS = 3
DEFAULT_MAX_GROUPS = 4

_PLAN_CACHE = _planner.register_cache("eval_plans", cap=64)
_GROUP_CACHE = _planner.register_cache("eval_groups", cap=16)

def netlist_digest(net: Netlist) -> str:
    """Content digest of a netlist's structure (the plan-cache key) —
    alias of :meth:`Netlist.content_digest`, shared with the sweep
    engine's pack/program caches."""
    return net.content_digest()


def clear_plan_caches() -> None:
    """Deprecated alias of :func:`repro.core.plan.clear_caches` — the
    unified registry clears *every* lowering/planning cache (functional
    IRs, eval plans, grouped tensors, sweep IR templates), where this
    name historically left the sweep-side caches live."""
    _planner.clear_caches()


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------


@dataclass
class PlanBucket:
    """One contiguous run of levels padded to its own envelope."""

    n_levels: int
    has_luts: bool
    has_chains: bool
    lut_ins: np.ndarray     # [l, M, 6] int32 (padded pins/rows -> CONST0)
    lut_tt_lo: np.ndarray   # [l, M] uint32
    lut_tt_hi: np.ndarray   # [l, M] uint32
    lut_out: np.ndarray     # [l, M] int32 (padded rows -> sink)
    ch_a: np.ndarray        # [l, C, B] int32
    ch_b: np.ndarray        # [l, C, B] int32
    ch_cin: np.ndarray      # [l, C] int32
    ch_sums: np.ndarray     # [l, C, B] int32 (padded -> sink)
    ch_cout: np.ndarray     # [l, C] int32 (chains without cout -> sink)
    ch_last: np.ndarray     # [l, C] int32 (index of the last real bit)

    def arrays(self):
        return (self.lut_ins, self.lut_tt_lo, self.lut_tt_hi, self.lut_out,
                self.ch_a, self.ch_b, self.ch_cin, self.ch_sums,
                self.ch_cout, self.ch_last)

    @property
    def shape(self) -> tuple[int, int, int, int]:
        """(levels, M, C, B) envelope of this bucket."""
        return (self.n_levels, self.lut_out.shape[1],
                self.ch_cout.shape[1], self.ch_a.shape[2])

    @property
    def padded_lut_rows(self) -> int:
        l, M, _, _ = self.shape
        return l * (M if self.has_luts else 0)

    @property
    def padded_chain_bits(self) -> int:
        l, _, C, B = self.shape
        return l * (C * B if self.has_chains else 0)


@dataclass
class FusedPlan:
    """Width-bucketed level tensors; ``sink = n_signals`` swallows padding."""

    n_signals: int
    n_levels: int
    buckets: tuple[PlanBucket, ...]
    real_luts: int = 0
    real_chain_bits: int = 0

    @property
    def sink(self) -> int:
        return self.n_signals

    @property
    def has_luts(self) -> bool:
        return any(bk.has_luts for bk in self.buckets)

    @property
    def has_chains(self) -> bool:
        return any(bk.has_chains for bk in self.buckets)

    @property
    def flags(self) -> tuple[tuple[bool, bool], ...]:
        """Static per-bucket (has_luts, has_chains) — part of the jit key."""
        return tuple((bk.has_luts, bk.has_chains) for bk in self.buckets)

    @property
    def envelope(self) -> tuple[int, int, int, int]:
        """The single worst-case (L, M, C, B) envelope (pre-bucketing).
        Dimensions whose side is absent are 0, not the array floor of 1 —
        a pure-LUT circuit must not be charged L phantom chain rows."""
        return (self.n_levels,
                max((bk.shape[1] if bk.has_luts else 0)
                    for bk in self.buckets),
                max((bk.shape[2] if bk.has_chains else 0)
                    for bk in self.buckets),
                max((bk.shape[3] if bk.has_chains else 0)
                    for bk in self.buckets))

    @property
    def padded_lut_rows(self) -> int:
        return sum(bk.padded_lut_rows for bk in self.buckets)

    @property
    def padded_chain_bits(self) -> int:
        return sum(bk.padded_chain_bits for bk in self.buckets)

    def arrays(self):
        return tuple(bk.arrays() for bk in self.buckets)


def _bucket_from_ir(ir: CircuitIR, i: int, j: int, M: int, C: int, B: int,
                    sink: int) -> PlanBucket:
    """Pad IR levels ``[i, j)`` to the bucket envelope ``[l, M, C, B]``."""
    l = max(j - i, 1)
    has_luts = M > 0
    has_chains = C > 0
    lut_ins = np.full((l, max(M, 1), 6), CONST0, dtype=np.int32)
    lut_tt_lo = np.zeros((l, max(M, 1)), dtype=np.uint32)
    lut_tt_hi = np.zeros((l, max(M, 1)), dtype=np.uint32)
    lut_out = np.full((l, max(M, 1)), sink, dtype=np.int32)
    ch_a = np.full((l, max(C, 1), max(B, 1)), CONST0, dtype=np.int32)
    ch_b = np.full((l, max(C, 1), max(B, 1)), CONST0, dtype=np.int32)
    ch_cin = np.full((l, max(C, 1)), CONST0, dtype=np.int32)
    ch_sums = np.full((l, max(C, 1), max(B, 1)), sink, dtype=np.int32)
    ch_cout = np.full((l, max(C, 1)), sink, dtype=np.int32)
    ch_last = np.zeros((l, max(C, 1)), dtype=np.int32)
    for t in range(i, min(j, ir.n_levels)):
        r = t - i
        ll, cl = ir.lut_levels[t], ir.chain_levels[t]
        m = ll.out.shape[0]
        if m:
            lut_ins[r, :m] = ll.ins
            lut_tt_lo[r, :m] = ll.tt_lo
            lut_tt_hi[r, :m] = ll.tt_hi
            lut_out[r, :m] = ll.out
        c = cl.cout.shape[0]
        if c:
            bb = cl.a_sig.shape[1]
            ch_a[r, :c, :bb] = cl.a_sig
            ch_b[r, :c, :bb] = cl.b_sig
            ch_cin[r, :c] = cl.cin_sig
            s = cl.sums.copy()
            s[s < 0] = sink
            ch_sums[r, :c, :bb] = s
            co = cl.cout.copy()
            co[co < 0] = sink
            ch_cout[r, :c] = co
            ch_last[r, :c] = cl.last
    return PlanBucket(n_levels=l, has_luts=has_luts, has_chains=has_chains,
                      lut_ins=lut_ins, lut_tt_lo=lut_tt_lo,
                      lut_tt_hi=lut_tt_hi, lut_out=lut_out, ch_a=ch_a,
                      ch_b=ch_b, ch_cin=ch_cin, ch_sums=ch_sums,
                      ch_cout=ch_cout, ch_last=ch_last)


def plan_from_ir(ir: CircuitIR,
                 max_buckets: int = DEFAULT_MAX_BUCKETS,
                 n_signals: int | None = None,
                 bounds=None, envelopes=None) -> FusedPlan:
    """Compile a :class:`CircuitIR` (functional or packed — only the
    functional columns are read) into width-bucketed level tensors.

    This is the evaluator's half of the one-lowering contract: the same
    IR object that the vectorized timing analyzer consumes drives the
    fused evaluation plan, with no re-levelization.  Pass ``bounds`` /
    ``envelopes`` to pad to a shared group layout (suite batching).
    """
    m, c, b = ir.level_profile()
    if not m:
        m, c, b = [0], [0], [0]
    if bounds is None:
        bounds = segment_levels(m, c, b, max_buckets)
    if envelopes is None:
        envelopes = _planner.bucket_envelopes(m, c, b, bounds)
    if n_signals is None:
        n_signals = ir.n_signals
    sink = n_signals
    buckets = tuple(_bucket_from_ir(ir, i, j, M, C, B, sink)
                    for (i, j), (M, C, B) in zip(bounds, envelopes))
    n_levels = sum(max(j - i, 1) for i, j in bounds) if bounds else 1
    return FusedPlan(
        n_signals=n_signals, n_levels=n_levels, buckets=buckets,
        real_luts=int(sum(lv.out.shape[0] for lv in ir.lut_levels)),
        real_chain_bits=int(sum((lv.sums >= 0).sum()
                                for lv in ir.chain_levels)))


def plan_netlist(net: Netlist,
                 max_buckets: int = DEFAULT_MAX_BUCKETS) -> FusedPlan:
    """Compile a netlist into width-bucketed level tensors (content-cached,
    via the content-cached functional :class:`CircuitIR`)."""
    digest = netlist_digest(net)
    key = (digest, max_buckets)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        return cached
    ir = lower_netlist_ir(net, digest=digest)
    plan = plan_from_ir(ir, max_buckets=max_buckets)
    _PLAN_CACHE.put(key, plan)
    return plan


# ---------------------------------------------------------------------------
# fused single-jit evaluation (multi-scan over buckets)
# ---------------------------------------------------------------------------


def _lut_outputs(vals, ins, tt_lo, tt_hi, use_pallas):
    """The level's LUT outputs, ``[M, N]``: its input lanes gathered from
    the value buffer, then one fused ``lut_eval6`` call."""
    from repro.kernels import ops

    gathered = vals[ins]                             # [M, 6, N]
    return ops.lut_eval6(gathered, tt_lo, tt_hi, use_pallas=use_pallas)


def _ripple(vals, a_idx, b_idx, cin_idx):
    """Every chain of the level rippled at once: the sums and the carries
    out of each bit, both ``[B, C, N]`` (bit-major)."""
    av = vals[a_idx]                                 # [C, B, N]
    bv = vals[b_idx]
    c0 = vals[cin_idx]                               # [C, N]

    def ripple(c, ab):
        aa, bb = ab
        s = aa ^ bb ^ c
        cy = (aa & bb) | (c & (aa ^ bb))
        return cy, (s, cy)

    _, (ss, cys) = jax.lax.scan(
        ripple, c0, (av.swapaxes(0, 1), bv.swapaxes(0, 1)))
    return ss, cys


def _fused_body(vals, xs, *, has_luts: bool, has_chains: bool,
                use_pallas: bool):
    """One level of a :class:`FusedPlan`: fused LUT kernel + stacked chain
    ripple, each output scattered to its signal's row.  ``vals`` is the
    ``[n_signals + 1, N]`` value buffer (last row = padding sink)."""
    (ins, tt_lo, tt_hi, out_idx, a_idx, b_idx, cin_idx, sums_idx, cout_idx,
     last_idx) = xs
    if has_luts:
        out = _lut_outputs(vals, ins, tt_lo, tt_hi, use_pallas)
        vals = vals.at[out_idx].set(out)
    if has_chains:
        ss, cys = _ripple(vals, a_idx, b_idx, cin_idx)
        vals = vals.at[sums_idx].set(ss.swapaxes(0, 1))
        # cout is the carry *after the chain's last real bit* — padded tail
        # bits add 0+0 and would zero the carry, so index, don't take last
        cout_v = jnp.take_along_axis(
            cys.swapaxes(0, 1), last_idx[:, None, None], axis=1)[:, 0]
        vals = vals.at[cout_idx].set(cout_v)
    return vals, None


def _slot_body(vals, xs, *, has_luts: bool, has_chains: bool,
               use_pallas: bool):
    """One level of a :class:`SlotPlan`: the same kernel and ripple, each
    output block written by one slice at the level's row ``base``."""
    base, ins, tt_lo, tt_hi, a_idx, b_idx, cin_idx, last_idx = xs
    if has_luts:
        out = _lut_outputs(vals, ins, tt_lo, tt_hi, use_pallas)
        vals = jax.lax.dynamic_update_slice(vals, out, (base, 0))
        base = base + ins.shape[0]
    if has_chains:
        ss, cys = _ripple(vals, a_idx, b_idx, cin_idx)
        B, C, N = ss.shape
        vals = jax.lax.dynamic_update_slice(
            vals, ss.reshape(B * C, N), (base, 0))
        cout_v = jnp.take_along_axis(cys, last_idx[None, :, None],
                                     axis=0)[0]
        vals = jax.lax.dynamic_update_slice(
            vals, cout_v, (base + B * C, 0))
    return vals, None


def _multi_scan(vals, bucket_arrays, flags, use_pallas, body=_fused_body):
    """Back-to-back lax.scans, one per width bucket, in topological order;
    ``body`` is the level step of the plan the arrays come from."""
    for (hl, hc), xs in zip(flags, bucket_arrays):
        step = functools.partial(body, has_luts=hl, has_chains=hc,
                                 use_pallas=use_pallas)
        vals, _ = jax.lax.scan(step, vals, xs)
    return vals


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("flags", "use_pallas"))
def _run_fused_batch(vals, bucket_arrays, *, flags, use_pallas):
    return jax.vmap(
        lambda v, arrs: _multi_scan(v, arrs, flags, use_pallas)
    )(vals, bucket_arrays)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _device_vals(pi_idx, pi_rows, *, n_rows):
    """The value buffer, built on the device: zeros, ``CONST1`` all ones,
    and each primary input's lanes at its signal row.  ``pi_idx[..., P]``
    holds the signal rows, ``pi_rows[..., P, N]`` their lanes; a padded
    slot's index (``>= n_rows``) writes nothing.  A leading axis of both
    is the group's member axis.

    ``CONST1`` goes in as one more scattered row: XLA folds a buffer that
    is constant (zeros with a row of ones) into the program as a literal
    of the buffer's size, which the compile cache then stores and loads.
    """
    def one(idx, rows):
        n = rows.shape[-1]
        idx = jnp.concatenate([jnp.full((1,), CONST1, dtype=idx.dtype), idx])
        rows = jnp.concatenate(
            [jnp.full((1, n), 0xFFFFFFFF, dtype=jnp.uint32), rows])
        return jnp.zeros((n_rows, n), dtype=jnp.uint32).at[idx].set(
            rows, mode="drop")
    if pi_idx.ndim == 1:
        return one(pi_idx, pi_rows)
    return jax.vmap(one)(pi_idx, pi_rows)


def _pi_slots(net: Netlist) -> dict[int, int]:
    """Each source's slot in the packed PI rows: its place in
    ``net.sources`` (the primary inputs, then any register's Q, which a
    combinational evaluation reads as a free input)."""
    return {s: j for j, s in enumerate(net.sources)}


def _fill_pi_rows(slots: list[dict[int, int]],
                  pi_lanes_list: list[dict[int, np.ndarray]],
                  n_pis: int, n_lane_words: int) -> np.ndarray:
    """``[len(slots), n_pis, N]`` host lanes of the primary inputs, each
    in its slot; an input missing from its dict stays 0.  A key that is
    not a primary input of its netlist raises ``ValueError``."""
    with span("repro.eval.fill") as sp:
        rows = np.zeros((len(slots), n_pis, n_lane_words), dtype=np.uint32)
        for r, (slot, lanes) in enumerate(zip(slots, pi_lanes_list)):
            for s, v in lanes.items():
                j = slot.get(s)
                if j is None:
                    raise ValueError(
                        f"signal {s} is not a primary input of its netlist")
                rows[r, j] = np.asarray(v, dtype=np.uint32)
        sp.set(bytes=rows.nbytes)
    return rows


def _put(rows: np.ndarray) -> jax.Array:
    with span("repro.eval.put", bytes=rows.nbytes):
        return jnp.asarray(rows).block_until_ready()


# ---------------------------------------------------------------------------
# envelope-grouped suite evaluation
# ---------------------------------------------------------------------------


def group_plans_by_envelope(plans, max_groups: int = DEFAULT_MAX_GROUPS,
                            signal_weight: float = 1.0) -> list[list[int]]:
    """Cluster plans (or any ``.envelope`` / ``.n_signals`` carriers, e.g.
    :class:`CircuitIR`) into <= ``max_groups`` compatible-envelope groups
    — delegated to the shared planner
    (:func:`repro.core.plan.group_by_envelope`, which the timing sweep
    uses too)."""
    return _planner.group_by_envelope(plans, max_groups=max_groups,
                                      signal_weight=signal_weight)


def grouping_padded_value_rows(plans, groups: list[list[int]]) -> dict:
    """Value-buffer padding accounting for a grouping: every member is
    padded to its group's largest ``n_signals``."""
    real = sum(p.n_signals for p in plans)
    padded = sum(len(g) * max(plans[i].n_signals for i in g) for g in groups)
    return {"real_rows": real, "padded_rows": padded,
            "waste": 1.0 - real / max(padded, 1)}


class GroupProgram(NamedTuple):
    """One envelope group's cached device program inputs."""

    n_sig: int                    # value rows per member, sink excluded
    stacked: tuple                # per-bucket stacked plan tensors
    flags: tuple                  # static per-bucket (has_luts, has_chains)
    member_plans: list            # each member's plan, padded to the group
    pi_index: jax.Array           # [members, P] int32 PI signal rows
    pi_slots: list                # per member: PI signal -> slot in P


def _build_group(nets: list[Netlist], max_buckets: int) -> GroupProgram:
    """Stack one envelope group's member plans into vmappable tensors.

    Bucket boundaries are recomputed on the group's combined width profile
    and every member is padded to the group envelope; each member's sink
    rows point at the shared ``n_sig`` row.  Each member's primary inputs
    are indexed in ``net.pis`` order, padded to the group's largest PI
    count with the out-of-range row ``n_sig + 1``.
    """
    irs = [lower_netlist_ir(net) for net in nets]
    n_sig = max(net.n_signals for net in nets)
    L = max(max(ir.n_levels for ir in irs), 1)
    m, c, b = _planner.combined_profile([ir.level_profile() for ir in irs],
                                        L)
    bounds = segment_levels(m, c, b, max_buckets)
    envelopes = _planner.bucket_envelopes(m, c, b, bounds)
    member_plans = [
        plan_from_ir(ir, n_signals=n_sig, bounds=bounds,
                     envelopes=envelopes)
        for ir in irs]
    flags = tuple(
        (any(p.buckets[bi].has_luts for p in member_plans),
         any(p.buckets[bi].has_chains for p in member_plans))
        for bi in range(len(bounds)))
    stacked = tuple(
        tuple(jnp.asarray(np.stack([np.asarray(p.buckets[bi].arrays()[ai])
                                    for p in member_plans]))
              for ai in range(10))
        for bi in range(len(bounds)))
    n_pis = max(len(net.sources) for net in nets)
    pi_index = np.full((len(nets), n_pis), n_sig + 1, dtype=np.int32)
    for row, net in enumerate(nets):
        pi_index[row, :len(net.sources)] = net.sources
    return GroupProgram(n_sig, stacked, flags, member_plans,
                        jnp.asarray(pi_index),
                        [_pi_slots(net) for net in nets])


def get_group_program(nets: list[Netlist],
                      max_buckets: int = DEFAULT_MAX_BUCKETS
                      ) -> GroupProgram:
    """Cached stacked device tensors for one envelope group of netlists."""
    key = (tuple(netlist_digest(net) for net in nets), max_buckets)
    cached = _GROUP_CACHE.get(key)
    if cached is None:
        cached = _build_group(nets, max_buckets)
        _GROUP_CACHE.put(key, cached)
    return cached


@dataclass
class SuiteProgram:
    """A suite's clustering + stacked device tensors, prepared once.

    ``run`` evaluates new lanes without re-digesting, re-clustering or
    re-uploading anything — the handle benchmark loops should reuse.
    Only the primary inputs' lanes cross to the device; the value buffer
    is built there.
    """

    n_signals: list[int]          # per input circuit
    names: list[str]
    groups: list[list[int]]       # member indices per envelope group
    programs: list[GroupProgram]
    stats: dict

    def run(self, pi_lanes_list: list[dict[int, np.ndarray]],
            n_lane_words: int, use_pallas: bool = True) -> list[np.ndarray]:
        """Each circuit's whole ``[n_signals, N]`` value buffer, on the
        host, in input order.

        The groups are pipelined: every group is filled, put and
        dispatched, its result's copy to the host started at once, before
        any result is fetched, so one group's copy back overlaps the device
        runs of the groups after it.  The smallest buffer goes first
        (Johnson's rule for two stages, the device run and the copy back,
        both about proportional to a group's buffer rows, the copy the
        longer): the link starts soonest and only the largest copy is left
        once the device is done.

        Spans: ``repro.eval.put`` until a group's PI rows are on the
        device; ``repro.eval.run`` around each dispatch and, when results
        are fetched, around the host's wait until that group's result is
        ready; ``repro.eval.get`` the wait for the rest of its copy
        (``bytes`` the buffer, ``queued`` the groups dispatched and not yet
        fetched, this one included).
        """
        order = sorted(range(len(self.groups)), key=lambda k: len(
            self.groups[k]) * (self.programs[k].n_sig + 1))
        pending = []
        for k in order:
            members, g = self.groups[k], self.programs[k]
            rows = _put(_fill_pi_rows(
                g.pi_slots, [pi_lanes_list[i] for i in members],
                g.pi_index.shape[1], n_lane_words))
            with span("repro.eval.run"):
                vals = _device_vals(g.pi_index, rows, n_rows=g.n_sig + 1)
                out = _run_fused_batch(vals, g.stacked, flags=g.flags,
                                       use_pallas=use_pallas)
                out.copy_to_host_async()
            pending.append((members, out))
        outs: list = [None] * len(self.n_signals)
        for j, (members, out) in enumerate(pending):
            with span("repro.eval.run"):
                out.block_until_ready()
            with span("repro.eval.get", queued=len(pending) - j) as sp:
                host = np.asarray(out)
                sp.set(bytes=host.nbytes)
            for row, i in enumerate(members):
                outs[i] = host[row, :self.n_signals[i]]
        return outs


def prepare_suite_program(nets: list[Netlist],
                          max_groups: int = DEFAULT_MAX_GROUPS,
                          max_buckets: int = DEFAULT_MAX_BUCKETS
                          ) -> SuiteProgram:
    """Cluster a suite into <= ``max_groups`` compatible-envelope groups and
    build (or fetch from the content cache) each group's stacked tensors."""
    plans = [plan_netlist(net, max_buckets=max_buckets) for net in nets]
    groups = group_plans_by_envelope(plans, max_groups=max_groups)
    programs = [get_group_program([nets[i] for i in members],
                                  max_buckets=max_buckets)
                for members in groups]
    stats = {"n_groups": len(groups), "groups": []}
    for members, g in zip(groups, programs):
        gp = g.member_plans[0]
        stats["groups"].append({
            "members": [nets[i].name for i in members],
            "n_buckets": len(gp.buckets),
            "bucket_shapes": [bk.shape for bk in gp.buckets],
            "padded_lut_rows": gp.padded_lut_rows * len(members),
            "padded_chain_bits": gp.padded_chain_bits * len(members),
        })
    return SuiteProgram(n_signals=[p.n_signals for p in plans],
                        names=[net.name for net in nets],
                        groups=groups, programs=programs, stats=stats)


def eval_netlist_jax(net: Netlist, pi_lanes: dict[int, np.ndarray],
                     n_lane_words: int,
                     use_pallas: bool = True) -> np.ndarray:
    """One circuit, evaluated as a suite of one; returns its value buffer
    ``vals[n_signals, n_lane_words]`` uint32.

    ``pi_lanes[signal]`` is a uint32 vector of packed test vectors, keyed
    by sources of ``net`` (its primary inputs, and any register's Q, which
    reads as a free input); any other key raises ``ValueError``, a missing
    source reads 0.
    """
    return prepare_suite_program([net]).run(
        [pi_lanes], n_lane_words, use_pallas=use_pallas)[0]


# ---------------------------------------------------------------------------
# cycle simulation of registered netlists
# ---------------------------------------------------------------------------

_SEQ_CACHE = _planner.register_cache("seq_programs", cap=16)


@dataclass
class SlotPlan:
    """A cycle program's private numbering of the value buffer, so that
    every write is one slice.

    Rows ``0, 1`` are ``CONST0`` / ``CONST1`` and the sources follow
    (``net.sources``: the primary inputs, then every register's Q): the
    *source block*, written once a cycle.  Then, per bucket and per level
    in scan order, one block per level: its ``M`` LUT outputs (real rows
    first), its ``C x B`` chain sums bit-major (the ripple's ``[B, C, N]``
    output as it is), then its ``C`` carries out.  Every read index maps
    through ``slot``; a signal that is neither a source nor written maps
    to ``CONST0`` and reads 0.  No caller sees a row number: the program
    returns the outputs only.
    """

    n_rows: int                   # value-buffer height
    flags: tuple                  # static per-bucket (has_luts, has_chains)
    buckets: tuple                # per bucket: (base, ins, tt_lo, tt_hi,
    #                               a, b, cin, last), base [l] int32
    slot: np.ndarray              # [n_signals] int32: each signal's row
    real_luts: int
    padded_lut_rows: int
    _dev: tuple | None = field(default=None, repr=False)

    def device_arrays(self):
        if self._dev is None:
            self._dev = tuple(tuple(jnp.asarray(a) for a in bk)
                              for bk in self.buckets)
        return self._dev


def slot_plan(plan: FusedPlan, sources) -> SlotPlan:
    """Renumber a :class:`FusedPlan`'s rows into level blocks (see
    :class:`SlotPlan`); its gathers, truth tables and padding stay."""
    src = np.asarray(sources, dtype=np.int64)
    slot = np.full(plan.n_signals + 1, CONST0, dtype=np.int64)
    slot[CONST1] = CONST1
    slot[src] = 2 + np.arange(src.size)
    top = 2 + src.size
    bases = []
    for bk in plan.buckets:
        l, M, C, B = bk.shape
        m = M if bk.has_luts else 0
        ch = C * B + C if bk.has_chains else 0
        base = top + (m + ch) * np.arange(l)
        if bk.has_luts:
            rows = base[:, None] + np.arange(M)
            real = bk.lut_out != plan.sink
            slot[bk.lut_out[real]] = rows[real]
        if bk.has_chains:
            s0 = base + m
            rows = (s0[:, None, None] + np.arange(C)[None, :, None]
                    + C * np.arange(B)[None, None, :])
            real = bk.ch_sums != plan.sink
            slot[bk.ch_sums[real]] = rows[real]
            rows = s0[:, None] + C * B + np.arange(C)
            real = bk.ch_cout != plan.sink
            slot[bk.ch_cout[real]] = rows[real]
        bases.append(base)
        top += l * (m + ch)
    slot = slot.astype(np.int32)
    buckets = tuple(
        (base.astype(np.int32), slot[bk.lut_ins], bk.lut_tt_lo,
         bk.lut_tt_hi, slot[bk.ch_a], slot[bk.ch_b], slot[bk.ch_cin],
         bk.ch_last)
        for base, bk in zip(bases, plan.buckets))
    return SlotPlan(n_rows=top, flags=plan.flags, buckets=buckets,
                    slot=slot[:plan.n_signals], real_luts=plan.real_luts,
                    padded_lut_rows=plan.padded_lut_rows)


class SeqProgram(NamedTuple):
    """One registered netlist's cycle program: its slot plan and the
    rows of its sinks in it, on the device."""

    plan: SlotPlan
    n_pis: int
    d_idx: jax.Array              # [R] int32 register D rows
    po_idx: jax.Array             # [O] int32 PO rows, ``net.pos`` order


def seq_program(net: Netlist) -> SeqProgram:
    """The cycle program of ``net`` (content-cached), on the slot plan of
    the combinational evaluator's fused plan."""
    key = netlist_digest(net)
    hit = _SEQ_CACHE.get(key)
    if hit is not None:
        return hit
    net.check_regs_connected()
    plan = slot_plan(plan_netlist(net), net.sources)

    def rows(sigs):
        return jnp.asarray(plan.slot[np.asarray(sigs, dtype=np.int64)
                                     .reshape(-1)])

    prog = SeqProgram(
        plan=plan, n_pis=len(net.pis), d_idx=rows([d for d, _ in net.regs]),
        po_idx=rows([s for bus in net.pos.values() for s in bus]))
    _SEQ_CACHE.put(key, prog)
    return prog


@functools.partial(jax.jit, static_argnames=("flags", "use_pallas",
                                             "n_rows"))
def _run_seq(pi_stream, d_idx, po_idx, bucket_arrays, *, flags, use_pallas,
             n_rows):
    """``lax.scan`` over cycles of a :class:`SlotPlan`, the value buffer
    and the register rows the carry.  A cycle writes the source block
    (``CONST0``, ``CONST1``, its PI rows, the registers' Q) by one slice,
    runs the fused bucket scans, whose levels write their blocks by slice
    too (:func:`_slot_body`), and gathers the next Q (the D rows) and the
    PO rows.  Every other row is rewritten before it is read, so the
    buffer is carried, not rebuilt.  Every register starts at 0.
    ``pi_stream`` is ``[T, P, N]``; returns ``[T, O, N]``.

    The combinational program (:func:`_run_fused_batch`) returns the
    whole buffer by signal id, so its levels scatter instead
    (:func:`_fused_body`)."""
    n = pi_stream.shape[-1]
    consts = jnp.broadcast_to(
        jnp.array([[0], [0xFFFFFFFF]], dtype=jnp.uint32), (2, n))

    def cycle(carry, pi_rows):
        vals, state = carry
        vals = jax.lax.dynamic_update_slice(
            vals, jnp.concatenate([consts, pi_rows, state]), (0, 0))
        vals = _multi_scan(vals, bucket_arrays, flags, use_pallas,
                           body=_slot_body)
        return (vals, vals[d_idx]), vals[po_idx]

    vals = jnp.zeros((n_rows, n), dtype=jnp.uint32)
    state = jnp.zeros((d_idx.shape[0], n), dtype=jnp.uint32)
    _, pos = jax.lax.scan(cycle, (vals, state), pi_stream)
    return pos


def run_sequential(prog: SeqProgram, pi_stream: np.ndarray,
                   use_pallas: bool = True) -> np.ndarray:
    """Simulate ``pi_stream`` (``[T, P, N]`` uint32 lanes of the primary
    inputs per cycle, ``net.pis`` order) and return the primary outputs'
    lanes per cycle, ``[T, O, N]`` uint32 in ``net.pos`` order.  The
    stream crosses to the device once and the outputs come back once: no
    state crosses between cycles.  The ``repro.seq.run`` span counts the
    value-buffer rows the call writes, by slice and by scatter."""
    if pi_stream.ndim != 3 or pi_stream.shape[1] != prog.n_pis:
        raise ValueError(f"PI stream of shape {pi_stream.shape}, the "
                         f"netlist has {prog.n_pis} primary inputs")
    plan = prog.plan
    with span("repro.seq.put", bytes=pi_stream.nbytes):
        dev = jnp.asarray(pi_stream, dtype=jnp.uint32).block_until_ready()
    # every row of the buffer is written by slice once a cycle
    with span("repro.seq.run", slice_rows=pi_stream.shape[0] * plan.n_rows,
              scatter_rows=0):
        out = _run_seq(dev, prog.d_idx, prog.po_idx, plan.device_arrays(),
                       flags=plan.flags, use_pallas=use_pallas,
                       n_rows=plan.n_rows).block_until_ready()
    with span("repro.seq.get") as sp:
        out = np.asarray(out)
        sp.set(bytes=out.nbytes)
    return out
