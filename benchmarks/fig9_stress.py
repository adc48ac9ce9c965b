"""Fig. 9 — packing stress test, plus the fused-evaluator perf workload.

500 adders + 0..500 unrelated 5-LUTs.  Paper: DD5 area stays flat until the
ALMs saturate; concurrently packed 5-LUTs saturate at ~375 (75 %).

The *layered* saturated stress circuit (500 adders + 500 LUTs feeding two
3x-smaller layers — a wide-then-narrow level profile) doubles as the
standard workload for the netlist-evaluation engine: ``run_eval_benchmark``
times the width-bucketed fused evaluator on it, proves pack/re-elaborate
equivalence through the ``core.flow`` pipeline, and reports the engine's
roofline terms — including the per-bucket padding waste next to the old
single-envelope waste.
"""
from __future__ import annotations

from repro.core import flow
from repro.core.alm import BASELINE, DD5
from repro.core.stress import run_packing_stress, packing_stress_circuit

from .common import Timer, emit, min_of_n

LUT_COUNTS = [0, 50, 100, 150, 200, 250, 300, 350, 400, 450, 500]


def run(verbose: bool = True):
    out = {}
    for arch in (BASELINE, DD5):
        res = run_packing_stress(arch, n_adders=500, lut_counts=LUT_COUNTS)
        out[arch.name] = res
        if verbose:
            for r in res:
                emit(f"fig9/{arch.name}/luts{r['n_luts']}", 0,
                     f"alms={r['alms']};area={r['area_mwta']:.0f};"
                     f"conc={r['concurrent']}")
    return out


def eval_workload(n_adders: int = 500, n_luts: int = 500, seed: int = 0,
                  depth: int = 3):
    """The canonical evaluation workload: the saturated Fig. 9 circuit,
    stacked ``depth`` layers deep (each 3x smaller) so the level-width
    profile exercises the evaluator's width buckets."""
    return packing_stress_circuit(n_adders=n_adders, n_luts=n_luts,
                                  seed=seed, depth=depth)


def run_eval_benchmark(n_lane_words: int = 8, use_pallas: bool = True,
                       reps: int = 3, check_equiv: bool = True,
                       verbose: bool = True) -> dict:
    """Time the evaluation of the stress workload.

    Returns a record with the best-of-``reps`` wall time (post-warmup, so
    it excludes the one-time compile), the evaluator's analytic roofline
    terms (bucketed and single-envelope padding waste side by side), and
    — when ``check_equiv`` — the pack/re-elaborate equivalence verdicts
    for baseline and DD5.
    """
    import jax

    from repro.core.equiv import check_pack_equivalence
    from repro.core.eval_jax import eval_netlist_jax, plan_netlist
    from .roofline import netlist_eval_terms

    net = eval_workload()
    lanes = flow.random_lanes(net, n_lane_words, seed=0)
    plan = plan_netlist(net)

    def bench(fn):
        # min-of-N perf_counter (shared gate timer): one untimed warmup
        # drains the jit compile, then the best of ``reps`` runs
        best, _ = min_of_n(lambda: jax.block_until_ready(fn()),
                           n=reps, warmup=1)
        return best

    t_fused = bench(lambda: eval_netlist_jax(
        net, lanes, n_lane_words, use_pallas=use_pallas))
    rec = {
        "workload": f"fig9_stress({net.name}: 500+ adders, 500+ luts, "
                    f"layered)",
        "n_lane_words": n_lane_words,
        "n_vectors": n_lane_words * 32,
        "use_pallas": use_pallas,
        "t_fused_s": t_fused,
        "roofline": netlist_eval_terms(net, n_lane_words, plan=plan),
    }
    if check_equiv:
        rec["equiv"] = {
            arch.name: check_pack_equivalence(net, arch, n_vectors=64)
            ["equivalent"] for arch in (BASELINE, DD5)
        }
    if verbose:
        emit("fig9_eval/fused", t_fused * 1e6,
             f"equiv={rec.get('equiv', 'skipped')}")
    return rec


def main():
    with Timer() as t:
        res = run()
    sat = res["dd5"][-1]["concurrent"]
    emit("fig9_stress", t.us,
         f"saturation_luts={sat};saturation_frac={sat/500:.2f}")
    res["eval_benchmark"] = run_eval_benchmark()
    return res


if __name__ == "__main__":
    main()
