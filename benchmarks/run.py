"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Sections:
  fig5  — CAD-flow validation (stock VTR vs improved synthesis)
  fig6  — DD5 vs baseline across suites (headline result)
  fig7  — DD5 vs DD6
  fig8  — routing-demand histogram
  fig9  — packing stress test
  table4 — end-to-end SHA stress test
  beyond — beyond-paper sparsity/width ablations
  sweep — arch-grid ADP frontier (bypass width x AddMux population),
          batched PackIR timing, oracle-gated
  place — placement-aware ADP frontier (grid placer + annealing
          refinement + wire-tier delays), gated on placed-oracle
          bit-identity and >= 2x placement reuse
  anneal — annealing placement refinement: per-circuit analytic-vs-
          annealed wirelength + CPD deltas, gated on legality,
          never-worse-than-seed, placed-oracle parity on annealed
          placements, and a suite geomean HPWL improvement >= 5%
  search — thousand-point successive-halving design-space search over
          the full arch grid, gated on winner oracle parity +
          equivalence and a >= 2x search-vs-dense cost ratio; plus the
          placed wire-delay-axis search (annealed placements, annealing
          wall in the rung ledger, >= 2x placement-reuse gate)
  serve — async batched flow serving: p50/p99 latency + throughput at
          1/8/32 concurrent clients, gated on serial bit-identity and
          coalesced warm throughput >= 2x the serial min-of-N baseline
  repack — incremental repack: a single-LUT edit on conv2d-fu served
          via dirty-set re-cluster + IR patch, gated on pack
          byte-identity, per-cluster equivalence of every touched LB,
          served-record parity, and a >= 2x delta-vs-full speedup
  kernels — Pallas kernel microbenchmarks (interpret mode on CPU)
  roofline — reads dry-run artifacts if present (see launch/dryrun.py)

Every section is failure-isolated — including its *import*: an exception
anywhere in one figure, an import error among them, reports a
``<section>,,failed(...)`` line on stderr, the run continues, and the
run exits non-zero.  Both ``main`` and ``smoke`` turn on JAX's
persistent compilation cache (:func:`repro.core.jax_cache.
enable_compile_cache`).

After each section the driver emits a ``<section>.timing_analysis`` CSV
row: the static-timing wall time (and call count) that section spent in
``repro.core.timing`` — the figure suites are packing-bound, and this row
is what proves it (the vectorized PackIR analyzer keeps the timing share
in the noise; see ``experiments/perf/timing_sweep.json`` for the
suite-scale sweep numbers).

``--smoke`` is the fast-tier CI entrypoint (also ``scripts/check.sh``):
runs ``pytest -m "not slow"``, a 2-point arch-grid sweep gated on oracle
bit-identity, the IR-parity step, a 2-circuit placement gate (placed
sweep bit-identical to the placed oracle + >= 2x placement reuse), a
2-circuit bounded-iteration anneal gate (grid-legal, wirelength <= the
analytic seed, placed-oracle parity on the annealed placements,
bit-deterministic re-anneal), a
2-rung / 8-point / 2-circuit search smoke (winner oracle parity +
equivalence, dense-vs-search cost ratio >= 1), and a flow-serving smoke
(8 concurrent clients over 2 circuits x 2 archs, every served record
bit-identical to serial ``pack_and_analyze``, coalesced warm throughput
>= the serial baseline), and a repack-delta smoke (a single-LUT edit on
conv2d-fu served via the dirty-set path: pack byte-identical to a fresh
``pack()``, every touched LB proven equivalent, served record
bit-identical to ``pack_and_analyze``), and exits non-zero on any
failure.  The run ends with the cache-registry table — per-cache
size/cap, hits, misses, evictions, and the derived hit rate from
``plan.cache_stats()`` — so a smoke log always shows where the run's
reuse actually came from.
"""
from __future__ import annotations

import importlib
import sys

SECTIONS = [
    ("fig5", "fig5_cad"),
    ("fig6", "fig6_dd5"),
    ("fig7", "fig7_dd6"),
    ("fig8", "fig8_congestion"),
    ("fig9", "fig9_stress"),
    ("table4", "table4_e2e"),
    ("beyond", "beyond_paper"),
    ("sweep", "sweep_frontier"),
    ("place", "place_sweep"),
    ("anneal", "anneal_refine"),
    ("search", "search_frontier"),
    ("serve", "serve_latency"),
    ("repack", "repack_delta"),
    ("kernels", "kernels"),
    ("roofline", "roofline"),
]


def _section(name: str, module: str) -> str:
    import time

    from repro.core.timing import read_timing_wall

    w0 = read_timing_wall()
    t0 = time.perf_counter()
    try:
        importlib.import_module(f".{module}", package=__package__).main()
        elapsed = time.perf_counter() - t0
        w1 = read_timing_wall()
        delta = w1["s"] - w0["s"]
        # Non-overlap invariant: scope-aware accounting (see
        # repro.core.timing.timing_section) guarantees each accounted
        # span commits once, so a section's timing delta can never
        # exceed the wall time the section actually ran for.  A
        # violation means a nested accounting site double-counted.
        # (explicit raise, not assert: must survive `python -O`)
        if delta > elapsed + 1e-6:
            raise RuntimeError(
                f"{name}: timing_analysis delta {delta:.3f}s exceeds "
                f"the section's elapsed {elapsed:.3f}s — TIMING_WALL "
                f"double-counted a nested section")
        print(f"{name}.timing_analysis,{delta * 1e6:.0f},"
              f"calls={w1['calls'] - w0['calls']}")
        return "ok"
    except Exception as e:  # noqa: BLE001 — report uniformly, keep going
        print(f"{name},,failed({type(e).__name__}: {e})", file=sys.stderr)
        return "failed"


def smoke() -> int:
    """Fast-tier check: ``pytest -m "not slow"`` + a 2-point arch-grid
    sweep proven bit-identical to the timing oracle + the IR-parity step
    (two circuits lowered ONCE each; eval and timing both proven against
    their oracles from the same CircuitIR object) + the 2-circuit
    placement gate (placed sweep bit-identical to the placed oracle,
    placement reuse >= 2x vs place-per-point) + the bounded-iteration
    anneal gate (legal, never-worse, placed-oracle parity,
    deterministic) + the 2-rung search smoke
    (winner oracle parity + equivalence, dense-vs-search ratio >= 1) +
    the flow-serving smoke (8 concurrent clients, 2 circuits x 2 archs;
    serial bit-identity + coalesced >= serial throughput) + the
    repack-delta smoke (single-LUT edit served via the dirty-set path,
    parity- and equivalence-gated)."""
    import os
    import subprocess

    from repro.core.jax_cache import enable_compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # the pytest child runs on the CPU, so it can never take a chip; and
    # this process does no jax work until the child has exited (every
    # gate below runs after subprocess.run returns) — one process per chip
    env["JAX_PLATFORMS"] = "cpu"
    print("== smoke: pytest fast tier ==", flush=True)
    tests = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "-m", "not slow"],
        cwd=root, env=env)
    enable_compile_cache()
    print("== smoke: 2-point arch-grid sweep ==", flush=True)
    try:
        from .sweep_frontier import run as sweep_run

        rec = sweep_run(smoke=True)
        sweep_ok = rec["oracle_match"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_sweep,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        sweep_ok = False
    print("== smoke: IR parity (one lowering serves eval + timing) ==",
          flush=True)
    try:
        from .ir_parity import run as ir_parity_run

        ir_ok = ir_parity_run()["oracle_match"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_ir_parity,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        ir_ok = False
    print("== smoke: 2-circuit placement parity + reuse gate ==",
          flush=True)
    try:
        from .place_sweep import run as place_run

        prec = place_run(smoke=True)
        place_ok = prec["pass_gate"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_place,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        place_ok = False
    print("== smoke: bounded-iteration anneal gate (2 circuits) ==",
          flush=True)
    try:
        from .anneal_refine import run as anneal_run

        arec = anneal_run(smoke=True)
        anneal_ok = arec["pass_gate"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_anneal,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        anneal_ok = False
    print("== smoke: 2-rung successive-halving search gate ==", flush=True)
    try:
        from .search_frontier import run as search_run

        srec = search_run(smoke=True)
        search_ok = srec["pass_gate"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_search,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        search_ok = False
    print("== smoke: flow-serving gate (8 clients, 2 circuits x 2 archs) ==",
          flush=True)
    try:
        from .serve_latency import run as serve_run

        vrec = serve_run(smoke=True)
        serve_ok = vrec["pass_gate"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_serve,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        serve_ok = False
    print("== smoke: repack-delta gate (single-LUT edit, dirty-set path) ==",
          flush=True)
    try:
        from .repack_delta import run as repack_run

        rrec = repack_run(smoke=True)
        repack_ok = rrec["pass_gate"]
    except Exception as e:  # noqa: BLE001
        print(f"smoke_repack,,failed({type(e).__name__}: {e})",
              file=sys.stderr)
        repack_ok = False
    _print_cache_table()
    ok = (tests.returncode == 0 and sweep_ok and ir_ok and place_ok
          and anneal_ok and search_ok and serve_ok and repack_ok)
    print(f"smoke,,{'ok' if ok else 'failed'}"
          f"(tests={'ok' if tests.returncode == 0 else 'fail'};"
          f"sweep={'ok' if sweep_ok else 'fail'};"
          f"ir_parity={'ok' if ir_ok else 'fail'};"
          f"place={'ok' if place_ok else 'fail'};"
          f"anneal={'ok' if anneal_ok else 'fail'};"
          f"search={'ok' if search_ok else 'fail'};"
          f"serve={'ok' if serve_ok else 'fail'};"
          f"repack={'ok' if repack_ok else 'fail'})")
    return 0 if ok else 1


def _print_cache_table() -> None:
    """The cache-registry table: every registered cache's occupancy and
    hit/miss/eviction counters with the derived hit rate — the smoke
    run's reuse ledger (counters survive ``clear_caches``, so this is
    cumulative over every gate above)."""
    from repro.core.plan import cache_stats

    stats = cache_stats()
    if not stats:
        return
    print("== caches ==", flush=True)
    print(f"{'cache':<20} {'size/cap':>9} {'hits':>7} {'misses':>7} "
          f"{'evict':>6} {'hit_rate':>8}")
    for name in sorted(stats):
        s = stats[name]
        print(f"{name:<20} {s['size']:>4}/{s['cap']:<4} {s['hits']:>7} "
              f"{s['misses']:>7} {s['evictions']:>6} {s['hit_rate']:>8.3f}")


def main() -> int:
    from repro.core.jax_cache import enable_compile_cache

    if "--smoke" in sys.argv[1:]:
        return smoke()
    enable_compile_cache()
    print("name,us_per_call,derived")
    status = {name: _section(name, mod) for name, mod in SECTIONS}
    failed = [name for name, st in status.items() if st == "failed"]
    if failed:
        print(f"sections_failed,,{';'.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
