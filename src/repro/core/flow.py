"""The unified CAD flow pipeline: synth → techmap → pack → equiv → eval.

Every benchmark driver and test drives the paper's flow through this
module instead of hand-rolling its own pack/analyze/verify/evaluate loop.
The stages:

* **synthesis + techmap** happen inside the circuit generators
  (``core.circuits``); the flow consumes finished :class:`Netlist`\\ s.
* **pack + analyze** — :func:`pack_and_analyze` packs under an
  architecture across placement seeds and averages the
  :func:`~repro.core.timing.analyze` metrics (the paper averages three
  seeds); :func:`pack_and_analyze_one` keeps the packed circuit for
  callers that need structural access (stress capacity sweeps).  Timing
  runs on the columnar :class:`~repro.core.pack_ir.PackIR` through the
  vectorized analyzer (bit-identical to the Python oracle) — figure
  drivers never re-walk the packed object graph.
* **design-space sweeps** — :func:`sweep_architectures` /
  :func:`sweep_frontier` drive :mod:`repro.core.sweep`: pack once per
  structural class, re-time the whole suite across an arch grid
  (:func:`repro.core.alm.arch_grid`) as one batched jit program per
  class, and reduce to geomean ADP-frontier rows.
* **equivalence gate** — :func:`run_circuit` optionally proves pack
  equivalence per arch through :mod:`repro.core.equiv` (symbolic fast
  path first, lane simulation as fallback), so any figure can be gated on
  "the comparison is apples-to-apples".
* **evaluation** — :func:`evaluate_suite` runs the width-bucketed fused
  engine (:mod:`repro.core.eval_jax`): it clusters a whole benchmark
  suite into a few compatible-envelope groups, so Kratos + Koios + VTR
  evaluate per arch as a handful of vmapped jit programs; plans and
  grouped tensors are content-cached, so repeated figures reuse
  compiles.  A single circuit is a suite of one
  (:func:`repro.core.eval_jax.eval_netlist_jax`).
* **cycle simulation** — :func:`evaluate_sequential` steps registered
  netlists over clock cycles on the device: one jitted scan over cycles
  per netlist, the fused bucket program its step (on a private row
  numbering, so each level writes its outputs by slice), the value
  buffer and the registers its carry
  (:func:`repro.core.eval_jax.run_sequential`).
* :func:`oracle_check` closes the loop: any JAX-side result can be
  proven bit-identical to the pure-Python ``eval_netlist`` oracle.

Ratios against a baseline arch (the shape of Figs. 5-7) come from
:func:`ratios_vs_baseline`; :func:`run_suites` maps the whole pipeline
over named suites.
"""
from __future__ import annotations

import random
from typing import Callable, Sequence

import numpy as np

from .alm import ARCHS, ArchParams
from .equiv import check_pack_equivalence
from .eval_jax import (DEFAULT_MAX_BUCKETS, DEFAULT_MAX_GROUPS,
                       SuiteProgram, prepare_suite_program, run_sequential,
                       seq_program)
from .netlist import Netlist, eval_netlist
from .packing import PackedCircuit, pack
from .spans import span
from .timing import analyze

#: the paper averages three placement seeds per figure
DEFAULT_SEEDS = (0, 1, 2)

#: metrics whose per-seed mean makes up a flow record
_METRIC_KEYS = ("alms", "area_mwta", "critical_path_ps", "adp",
                "concurrent_luts", "lbs")


def _arch(arch: str | ArchParams) -> ArchParams:
    return ARCHS[arch] if isinstance(arch, str) else arch


# ---------------------------------------------------------------------------
# pack + analyze
# ---------------------------------------------------------------------------


def pack_and_analyze_one(net: Netlist, arch: str | ArchParams,
                         seed: int = 0) -> tuple[PackedCircuit, dict]:
    """One pack at one seed, returning both the packed circuit and its
    analysis — for flows that need structural access (capacity sweeps)."""
    packed = pack(net, _arch(arch), seed=seed)
    return packed, analyze(packed)


def pack_and_analyze(net: Netlist, arch: str | ArchParams,
                     seeds: Sequence[int] = DEFAULT_SEEDS) -> dict:
    """Average :func:`analyze` metrics over placement seeds."""
    acc: dict[str, float] = {}
    for s in seeds:
        r = analyze(pack(net, _arch(arch), seed=s))
        for k in _METRIC_KEYS:
            acc[k] = acc.get(k, 0.0) + r[k] / len(seeds)
    acc["adders"] = net.n_adders
    acc["luts"] = net.n_luts
    return acc


def run_circuit(net: Netlist, archs: Sequence[str | ArchParams],
                seeds: Sequence[int] = DEFAULT_SEEDS,
                check_equiv: bool = False, n_vectors: int = 64,
                equiv_method: str = "auto") -> dict[str, dict]:
    """Pack + analyze one circuit under several archs, optionally gated on
    pack equivalence.  Returns ``{arch_name: metrics}``; with
    ``check_equiv`` each record carries ``equivalent`` / ``equiv_method``
    and a non-equivalent pack raises ``AssertionError`` — a figure must
    not silently average a corrupted pack.
    """
    out: dict[str, dict] = {}
    for arch in archs:
        ap = _arch(arch)
        rec = pack_and_analyze(net, ap, seeds=seeds)
        if check_equiv:
            rep = check_pack_equivalence(net, ap, seed=seeds[0],
                                         n_vectors=n_vectors,
                                         method=equiv_method)
            if not rep["equivalent"]:
                if equiv_method == "symbolic" and not rep["mismatches"]:
                    # incomplete proof, not a disproof — name it as such
                    raise AssertionError(
                        f"{net.name}@{ap.name}: symbolic proof incomplete "
                        f"({len(rep.get('fallback', []))} unclosed cones); "
                        f"use equiv_method='auto' to simulate the residue")
                raise AssertionError(
                    f"{net.name}@{ap.name}: pack is NOT equivalent "
                    f"({rep['mismatches'][:1]})")
            rec["equivalent"] = True
            rec["equiv_method"] = rep.get("method", "simulate")
        out[ap.name] = rec
    return out


def sweep_architectures(suites_or_nets, archs=None, seed: int = 0,
                        backend: str = "jax", max_buckets: int = 3,
                        max_groups: int = 4,
                        packs: dict | None = None,
                        programs: dict | None = None,
                        prefixes: dict | None = None,
                        grid_axes: dict | None = None,
                        place: bool = False,
                        refine: str | None = "anneal"):
    """Design-space sweep over an architecture grid (see
    :func:`repro.core.sweep.sweep_suite`).  ``archs`` defaults to the
    full bypass-width x crossbar-population grid; pass any list of
    :class:`~repro.core.alm.ArchParams` rows (e.g. the canonical
    baseline/DD5/DD6 triple plus ablations), or ``grid_axes`` — keyword
    arguments for :func:`repro.core.alm.arch_grid` (e.g.
    ``{"alms_per_lb": (8, 10), "lb_inputs": (48, 60)}``) — to grow the
    grid along the structural cluster-geometry axes.

    ``max_groups`` (the timing-program envelope-grouping knob) is
    forwarded verbatim: a flow caller can now both match a direct
    ``sweep_suite`` configuration and hit a ``programs`` cache warmed
    with a non-default grouping.  ``packs``/``programs``/``prefixes``
    are the caller-owned content-keyed caches of ``sweep_suite``.
    ``place=True`` grid-places every circuit and includes the wire-tier
    delay term (placements registry-cached per placement key, anneal-
    refined by default — ``refine`` forwards to
    :func:`repro.core.sweep.sweep_suite`; see :mod:`repro.core.place`
    and :mod:`repro.core.anneal`)."""
    from .alm import arch_grid
    from .sweep import sweep_suite

    if archs is None:
        archs = arch_grid(**(grid_axes or {}))
    elif grid_axes is not None:
        raise ValueError("pass either archs or grid_axes, not both")
    return sweep_suite(suites_or_nets, archs, seed=seed, backend=backend,
                       max_buckets=max_buckets, max_groups=max_groups,
                       packs=packs, programs=programs, prefixes=prefixes,
                       place=place, refine=refine)


def sweep_frontier(result, baseline: str | None = None):
    """Geomean area/cpd/ADP ratio rows vs a baseline grid point."""
    from .sweep import adp_frontier

    return adp_frontier(result, baseline=baseline)


def search_design_space(suites_or_nets, archs=None, seed: int = 0,
                        eta: int = 4, min_survivors: int = 8,
                        allocation: str = "halving",
                        budget: int | None = None,
                        baseline: str | None = None,
                        backend: str = "numpy", verify: bool = False,
                        **search_kwargs):
    """Pareto-aware successive-halving search over an arch grid (see
    :func:`repro.core.search.search_archs`).  ``archs`` defaults to the
    *full* design-space cross-product
    (:func:`repro.core.alm.full_arch_grid`, ~2000 points) and
    ``baseline`` to the grid's ``b0`` row when present.  ``verify=True``
    additionally proves every Pareto winner oracle-bit-identical and
    equivalence-gated (:func:`repro.core.search.verify_winners`) and
    attaches the report as ``result.verify``."""
    from .alm import full_arch_grid
    from .search import search_archs, verify_winners
    from .sweep import _flatten

    if archs is None:
        archs = full_arch_grid()
    if baseline is None and any(a.name == "b0" for a in archs):
        baseline = "b0"
    _, nets = _flatten(suites_or_nets)
    result = search_archs(nets, archs, seed=seed, eta=eta,
                          min_survivors=min_survivors,
                          allocation=allocation, budget=budget,
                          baseline=baseline, backend=backend,
                          **search_kwargs)
    if verify:
        result.verify = verify_winners(result, nets, archs, seed=seed)
    return result


def ratios_vs_baseline(per_arch: dict[str, dict], baseline: str = "baseline",
                       keys: Sequence[str] = ("area_mwta",
                                              "critical_path_ps", "adp")
                       ) -> dict[str, dict[str, float]]:
    """Per-arch metric ratios against ``per_arch[baseline]`` (Figs. 5-7)."""
    base = per_arch[baseline]
    return {name: {k: rec[k] / base[k] for k in keys}
            for name, rec in per_arch.items() if name != baseline}


def run_suites(suites: dict[str, list[Netlist]],
               archs: Sequence[str | ArchParams],
               seeds: Sequence[int] = DEFAULT_SEEDS,
               check_equiv: bool = False,
               per_circuit: Callable[[str, Netlist, dict], None]
               | None = None) -> dict[str, list[dict]]:
    """Map :func:`run_circuit` over named suites.

    Returns ``{suite: [{"net": name, "per_arch": {...}}, ...]}``;
    ``per_circuit(suite, net, per_arch)`` is an optional progress hook
    (benchmark drivers use it to emit CSV rows as results arrive).
    """
    out: dict[str, list[dict]] = {}
    for suite_name, nets in suites.items():
        rows = []
        for net in nets:
            per_arch = run_circuit(net, archs, seeds=seeds,
                                   check_equiv=check_equiv)
            rows.append({"net": net.name, "per_arch": per_arch})
            if per_circuit is not None:
                per_circuit(suite_name, net, per_arch)
        out[suite_name] = rows
    return out


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------


def random_lanes(net: Netlist, n_lane_words: int,
                 seed: int = 0) -> dict[int, np.ndarray]:
    """Random packed test vectors for every PI of ``net``."""
    rng = random.Random(seed)
    return {s: np.array([rng.getrandbits(32) for _ in range(n_lane_words)],
                        dtype=np.uint32) for s in net.pis}


def prepare_suite(nets: list[Netlist],
                  max_groups: int = DEFAULT_MAX_GROUPS,
                  max_buckets: int = DEFAULT_MAX_BUCKETS) -> SuiteProgram:
    """One-time suite preparation (clustering + stacked device tensors);
    reuse the returned program across :func:`evaluate_suite` calls."""
    return prepare_suite_program(nets, max_groups=max_groups,
                                 max_buckets=max_buckets)


def evaluate_suite(nets: list[Netlist],
                   pi_lanes_list: list[dict[int, np.ndarray]],
                   n_lane_words: int, use_pallas: bool = True,
                   max_groups: int = DEFAULT_MAX_GROUPS,
                   max_buckets: int = DEFAULT_MAX_BUCKETS,
                   program: SuiteProgram | None = None
                   ) -> tuple[list[np.ndarray], dict]:
    """Whole-suite evaluation as <= ``max_groups`` vmapped jit programs:
    the circuits' plans are clustered into compatible-envelope groups
    (:func:`repro.core.eval_jax.group_plans_by_envelope`) and each group's
    members are padded to its bucketed envelope.  Pass a ``program`` from
    :func:`prepare_suite` to skip planning and clustering.

    Returns ``(per-circuit vals arrays in input order, stats)``: stats
    records the envelope groups, their bucket shapes and padded-row
    counts, and ``mode``, always ``"grouped"``.  The benchmark's eval cell
    reads ``mode``, ``n_groups`` and each group's ``padded_lut_rows``.
    """
    with span("repro.eval.call", circuits=len(nets),
              lane_words=int(n_lane_words)):
        with span("repro.eval.plan"):
            if program is None:
                program = prepare_suite_program(nets, max_groups=max_groups,
                                                max_buckets=max_buckets)
        outs = program.run(pi_lanes_list, n_lane_words, use_pallas=use_pallas)
    return outs, dict(program.stats, mode="grouped")


def evaluate_sequential(nets: list[Netlist], pi_streams: list[np.ndarray],
                        use_pallas: bool = True
                        ) -> tuple[list[np.ndarray], dict]:
    """Cycle-by-cycle simulation of registered netlists on the device.

    ``pi_streams[i]`` is ``[T, len(nets[i].pis), N]`` uint32: each primary
    input's packed lanes (32 independent vectors a word) in each of ``T``
    cycles, in ``net.pis`` order.  Every register starts at 0.  Returns
    ``(outs, stats)``: ``outs[i]`` is ``[T, n_pos, N]`` uint32, the
    primary outputs' lanes in each cycle (in ``net.pos`` bus order, before
    the clock edge that ends the cycle), equal to
    :func:`repro.core.netlist.eval_sequential`; ``stats`` counts the step
    programs' real and padded LUT rows."""
    programs = [seq_program(n) for n in nets]
    outs = [run_sequential(prog, np.asarray(stream, dtype=np.uint32),
                           use_pallas=use_pallas)
            for prog, stream in zip(programs, pi_streams)]
    stats = {"real_lut_rows": sum(p.plan.real_luts for p in programs),
             "padded_lut_rows": sum(p.plan.padded_lut_rows
                                    for p in programs)}
    return outs, stats


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve(requests, **server_kwargs):
    """Serve a list of :class:`~repro.core.serve_flow.FlowRequest`\\ s
    through one async batched :class:`~repro.core.serve_flow.FlowServer`
    (coalescing window, request dedup, batched timing/eval programs,
    bounded multi-tenant caches) and return
    :class:`~repro.core.serve_flow.FlowResult`\\ s in request order.
    Every record is bit-identical to ``pack_and_analyze(net, arch,
    seeds=(seed,))`` — see :mod:`repro.core.serve_flow`."""
    from .serve_flow import serve_requests

    return serve_requests(requests, **server_kwargs)


def _lanes_int(words) -> int:
    """Packed uint32 lane words -> one int, word 0 in the low bits."""
    return int.from_bytes(np.asarray(words, dtype="<u4").tobytes(), "little")


def po_mismatches(net: Netlist, pi_lanes: dict[int, np.ndarray], vals,
                  n_lane_words: int) -> list[int]:
    """Primary-output signals whose lanes differ from the Python oracle.
    ``vals[s]`` is signal ``s``'s ``[n_lane_words]`` uint32 lanes (a
    value buffer or a ``{signal: lanes}`` mapping).  Every lane is an
    independent vector, so one oracle pass over
    ``32 * n_lane_words``-bit ints covers all words."""
    width = 32 * n_lane_words
    ref = eval_netlist(net, {s: _lanes_int(pi_lanes[s][:n_lane_words])
                             for s in net.pis}, width)
    mask = (1 << width) - 1
    return [s for bus in net.pos.values() for s in bus
            if _lanes_int(vals[s][:n_lane_words]) != ref[s] & mask]


def oracle_check(net: Netlist, pi_lanes: dict[int, np.ndarray],
                 vals, n_lane_words: int) -> bool:
    """Prove a JAX-side result bit-identical to the Python oracle on every
    primary output (all lane words)."""
    return not po_mismatches(net, pi_lanes, vals, n_lane_words)
