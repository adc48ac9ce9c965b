#!/usr/bin/env python3
"""Run the Double Duty CAD flow once on one TPU chip and check every result.

    python chip_smoke.py                          # one TPU chip, full suite
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny  # CPU rehearsal, cut suite

Everything runs in this one process: a chip belongs to one process at a
time, so no child process ever touches JAX.  The phases drive the flow's
own entry points over the Kratos + Koios + VTR suites at their default
sizes (17 circuits, 143-9,714 LUTs):

* ``eval``   — :func:`repro.core.flow.evaluate_suite` with the Pallas
  ``lut_eval6`` kernel at 4 and 128 lane words.  Every primary output
  equals :func:`repro.core.netlist.eval_netlist`, and the evaluation
  programs carry the compiled Mosaic kernel (``tpu_custom_call``).
* ``timing`` — :func:`repro.core.flow.sweep_architectures` over baseline,
  DD5 and DD6 with the jax timing program.  Every critical path and area
  equals :func:`repro.core.timing.analyze_oracle` bit for bit.
* ``place``  — ``place_ir(backend="jax", refine="anneal")`` on two suite
  circuits: grid-legal, wirelength no worse than the analytic seed, and
  placed jax timing equal to ``analyze_placed_oracle`` at a nonzero wire
  profile; the annealer refines the same seed placement identically on
  the chip and on the host CPU.
* ``serve``  — a :class:`~repro.core.serve_flow.FlowServer` (jax timing)
  answers 8 concurrent requests over 4 circuits x {baseline, DD5} with
  area, timing and eval.  Each record equals serial ``pack_and_analyze``
  and each eval equals ``eval_netlist``.

Each phase prints one line with the device, its shapes, the number of
programs compiled, its wall seconds (one cold run, compiles included) and
whether it passed.  The last line is one JSON object naming the device.
Without a TPU the script exits non-zero before any phase; ``--tiny`` is
the only exception, runs a cut suite, and says so on every line.  Any
failed phase exits non-zero, and the JSON line is not printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: programs compiled (or loaded from the persistent cache) / cache hits
COUNTS = {"compiles": 0, "cache_hits": 0}

#: circuits of the placement and serving phases
PLACE_CIRCUITS = ("gemmt-fu", "tpu-like")
SERVE_CIRCUITS = ("conv1d-fu", "dla-like", "or1200-like", "sha-like")
#: seed of the circuits, lanes, packs and placements
SEED = 0


def _count_compiles() -> None:
    import jax

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            COUNTS["compiles"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            COUNTS["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _eval_programs_have_kernel(nets, n_words: int) -> bool:
    """Re-lower the evaluation programs ``evaluate_suite`` just ran (same
    plans, same grouping) and check each carries the Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core import eval_jax, flow

    def vals(*lead):
        return jax.ShapeDtypeStruct(lead + (n_words,), jnp.uint32)

    prog = flow.prepare_suite(nets)
    texts = [eval_jax._run_fused_batch.lower(
        vals(len(members), g.n_sig + 1), g.stacked, flags=g.flags,
        use_pallas=True).as_text()
        for members, g in zip(prog.groups, prog.programs)]
    return all("tpu_custom_call" in t for t in texts)


def phase_eval(suites, seed: int, lane_words, on_chip: bool) -> tuple:
    from repro.core import flow

    nets = [n for ns in suites.values() for n in ns]
    shapes, bad = [], []
    for n_words in lane_words:
        lanes = [flow.random_lanes(n, n_words, seed=seed + i)
                 for i, n in enumerate(nets)]
        outs, stats = flow.evaluate_suite(nets, lanes, n_words,
                                          use_pallas=True)
        for net, ln, v in zip(nets, lanes, outs):
            if flow.po_mismatches(net, ln, v, n_words):
                bad.append(f"{net.name}@{n_words}w")
        if on_chip and not _eval_programs_have_kernel(nets, n_words):
            bad.append(f"no Mosaic kernel in the {n_words}-word programs")
        shapes.append(f"{n_words}w:{stats['n_groups']}")
    luts = [n.n_luts for n in nets]
    detail = (f"circuits={len(nets)} luts={min(luts)}-{max(luts)} "
              f"programs={','.join(shapes)}")
    return not bad, detail, bad


def phase_timing(suites, seed: int) -> tuple:
    from repro.core import flow
    from repro.core.alm import ARCHS
    from repro.core.packing import pack
    from repro.core.timing import analyze_oracle

    archs = [ARCHS["baseline"], ARCHS["dd5"], ARCHS["dd6"]]
    res = flow.sweep_architectures(suites, archs=archs, seed=seed,
                                   backend="jax")
    nets = [n for ns in suites.values() for n in ns]   # the sweep's order
    bad, max_diff = [], 0.0
    for g, net in enumerate(nets):
        for k, arch in enumerate(archs):
            want = analyze_oracle(pack(net, arch, seed=seed))
            got = res.records[g][k]
            diff = abs(got["critical_path_ps"] - want["critical_path_ps"])
            max_diff = max(max_diff, diff)
            if (got["critical_path_ps"] != want["critical_path_ps"]
                    or got["area_mwta"] != want["area_mwta"]):
                bad.append(f"{net.name}@{arch.name}")
    dd5 = next(r for r in flow.sweep_frontier(res, baseline="baseline")
               if r["arch"] == "dd5")
    detail = (f"records={len(nets)}x{len(archs)} oracle_mismatches="
              f"{len(bad)} max_abs_diff_ps={max_diff!r} "
              f"dd5_vs_baseline: area={100 * (dd5['area_mwta'] - 1):+.2f}% "
              f"adp={100 * (dd5['adp'] - 1):+.2f}%")
    return not bad, detail, bad


def _refine_on_each_device(ir, arch, base, seed: int, steps, mode) -> bool:
    """Whether the jax annealer gives the same placement on the default
    device and on the host CPU."""
    import jax
    import numpy as np

    from repro.core.anneal import refine_placement

    def run():
        return refine_placement(ir, arch, base, seed=seed, mode=mode,
                                backend="jax", steps=steps)

    here = run()
    with jax.default_device(jax.devices("cpu")[0]):
        host = run()
    return (np.array_equal(here.lb_x, host.lb_x)
            and np.array_equal(here.lb_y, host.lb_y))


def phase_place(suites, seed: int, steps) -> tuple:
    import numpy as np

    from repro.core.alm import make_arch
    from repro.core.circuit_ir import apply_placement
    from repro.core.packing import pack
    from repro.core.place import place_ir
    from repro.core.timing import analyze_placed_oracle
    from repro.core.timing_vec import analyze_ir

    wired = make_arch("dd5_wired", bypass_inputs=2, addmux_fanin=10,
                      t_wire_hop1=25.0, t_wire_hop2=40.0, t_wire_long=120.0)
    by_name = {n.name: n for ns in suites.values() for n in ns}
    bad, rows = [], []
    for name in PLACE_CIRCUITS:
        packed = pack(by_name[name], wired, seed=seed)
        ir = packed.lower_ir()
        base = place_ir(ir, wired, seed, backend="jax")
        ann = place_ir(ir, wired, seed, backend="jax", refine="anneal",
                       anneal_steps=steps)
        slots = set(zip(ann.lb_x.tolist(), ann.lb_y.tolist()))
        legal = (len(slots) == ann.n_lbs
                 and (ann.grid_w, ann.grid_h) == (base.grid_w, base.grid_h)
                 and bool(np.all((ann.lb_x >= 0) & (ann.lb_x < ann.grid_w)
                                 & (ann.lb_y >= 0)
                                 & (ann.lb_y < ann.grid_h))))
        wl0, wl1 = base.wirelength(ir), ann.wirelength(ir)
        got = analyze_ir(apply_placement(ir, ann), wired, backend="jax")
        parity = got == analyze_placed_oracle(packed, ann)
        # the int32 annealer refines one seed placement identically on
        # this device and on the host CPU, in both weighting modes
        same = all(_refine_on_each_device(ir, wired, base, seed, steps,
                                          mode)
                   for mode in ("anneal", "anneal_timing"))
        if not (legal and wl1 <= wl0 and parity and same):
            bad.append(f"{name}(legal={legal},wl={wl0}->{wl1},"
                       f"parity={parity},same_on_cpu={same})")
        rows.append(f"{name}:{ann.grid_w}x{ann.grid_h},lbs={ann.n_lbs},"
                    f"wl={wl0}->{wl1}")
    return not bad, " ".join(rows), bad


def phase_serve(suites, seed: int) -> tuple:
    from repro.core import flow
    from repro.core.flow import _METRIC_KEYS, pack_and_analyze
    from repro.core.serve_flow import FlowRequest

    by_name = {n.name: n for ns in suites.values() for n in ns}
    n_words = 4
    reqs = [FlowRequest(by_name[name], arch,
                        analyses=("area", "timing", "eval"), seed=seed,
                        n_lane_words=n_words, lanes_seed=seed)
            for name in SERVE_CIRCUITS for arch in ("baseline", "dd5")]
    results = flow.serve(reqs)
    bad = []
    for req, res in zip(reqs, results):
        ref = pack_and_analyze(req.net, req.arch, seeds=(seed,))
        if any(res.record[k] != ref[k] for k in _METRIC_KEYS):
            bad.append(f"{req.net.name}@{req.arch}:record")
        po = {s: res.analyses["eval"][name][i]
              for name, bus in req.net.pos.items()
              for i, s in enumerate(bus)}
        lanes = flow.random_lanes(req.net, n_words, seed=req.lanes_seed)
        if flow.po_mismatches(req.net, lanes, po, n_words):
            bad.append(f"{req.net.name}@{req.arch}:eval")
    batches = len({r.batch["id"] for r in results})
    detail = (f"requests={len(reqs)} batches={batches} "
              f"circuits={len(SERVE_CIRCUITS)} archs=baseline,dd5")
    return not bad, detail, bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal on a cut suite (not a chip run)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    tag = "[tiny: cut suite, not a chip run] " if args.tiny else ""
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU found — JAX platform is "
              f"{dev.platform!r}; pass --tiny for the CPU rehearsal",
              file=sys.stderr)
        return 2

    from repro.core.circuits import koios_suite, kratos_suite, vtr_suite
    from repro.core.jax_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _count_compiles()
    print(f"{tag}device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)

    scale = 0.25 if args.tiny else 1.0
    t0 = time.perf_counter()
    suites = {"kratos": kratos_suite(scale=scale, seed=SEED),
              "koios": koios_suite(scale=scale, seed=SEED),
              "vtr": vtr_suite(scale=scale, seed=SEED)}
    print(f"{tag}phase=setup circuits={sum(map(len, suites.values()))} "
          f"scale={scale} wall_s={time.perf_counter() - t0:.3f}",
          flush=True)

    phases = [
        ("eval", lambda: phase_eval(suites, SEED,
                                    (4, 8) if args.tiny else (4, 128),
                                    on_chip=dev.platform == "tpu")),
        ("timing", lambda: phase_timing(suites, SEED)),
        ("place", lambda: phase_place(suites, SEED,
                                      24 if args.tiny else None)),
        ("serve", lambda: phase_serve(suites, SEED)),
    ]
    where = "chip" if dev.platform == "tpu" else dev.platform
    failed = []
    for name, run in phases:
        c0, h0 = COUNTS["compiles"], COUNTS["cache_hits"]
        t0 = time.perf_counter()
        try:
            ok, detail, bad = run()
        except Exception as e:  # noqa: BLE001 — report the phase, go on
            traceback.print_exc()
            ok, detail, bad = False, f"raised {type(e).__name__}: {e}", []
        wall = time.perf_counter() - t0
        print(f"{tag}phase={name} device={dev.platform}:{dev.device_kind} "
              f"{detail} compiles={COUNTS['compiles'] - c0} "
              f"cache_hits={COUNTS['cache_hits'] - h0} "
              f"wall_s={wall:.3f} (one cold {where} run, compiles included) "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if bad:
            print(f"{tag}phase={name} failures: {'; '.join(bad[:20])}",
                  flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"{tag}chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if args.tiny:
        print(f"{tag}chip_smoke: all phases passed on {dev.platform}",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
