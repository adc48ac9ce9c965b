"""Smoke IR-parity check: ONE CircuitIR lowering serves eval AND timing.

The unified substrate's core contract (``repro/core/circuit_ir.py``): a
packed circuit is lowered exactly once per (content digest, structural
class), and that single object drives both the fused evaluator (via
``eval_jax.plan_from_ir`` — the functional columns) and the vectorized
static-timing analyzer (``timing_vec.analyze_ir`` — the placement
columns).  This check packs two small circuits, lowers each once, and
proves from the *same IR object*:

* the evaluation plan equal, tensor for tensor, to the one the evaluator
  runs for the netlist, and that evaluation bit-identical to the
  pure-python ``eval_netlist`` oracle on every primary output
  (``flow.oracle_check``);
* the timing record bit-identical to ``timing.analyze_oracle``;
* the lowering counters show exactly one functional lowering per
  circuit and one placement patch per (circuit, class) — no duplicate
  lowering anywhere on the path.

Run by ``scripts/check.sh`` / ``python -m benchmarks.run --smoke``.
"""
from __future__ import annotations

import numpy as np

from repro.core import flow
from repro.core.alm import ARCHS
from repro.core.circuit_ir import read_lower_counts, reset_lower_counts
from repro.core.circuits import kratos_gemm, sha_like
from repro.core.eval_jax import eval_netlist_jax, plan_from_ir, plan_netlist
from repro.core.packing import pack
from repro.core.plan import clear_caches
from repro.core.timing import analyze_oracle
from repro.core.timing_vec import analyze_ir

from .common import emit

N_LANE_WORDS = 2


def run(verbose: bool = True) -> dict:
    nets = [kratos_gemm(m=4, n=4, width=4, sparsity=0.5),
            sha_like(rounds=1)]
    arch = ARCHS["dd5"]
    clear_caches()
    reset_lower_counts()
    eval_ok = timing_ok = True
    for net in nets:
        packed = pack(net, arch, seed=0)
        ir = packed.lower_ir()                      # the ONE lowering
        # eval lane: the plan built from the same IR object is the plan
        # the evaluator runs for the netlist
        got, want = plan_from_ir(ir), plan_netlist(net)
        eval_ok &= got.flags == want.flags and all(
            np.array_equal(x, y) for bg, bw in zip(got.arrays(), want.arrays())
            for x, y in zip(bg, bw))
        lanes = flow.random_lanes(net, N_LANE_WORDS, seed=0)
        vals = eval_netlist_jax(net, lanes, N_LANE_WORDS)
        eval_ok &= flow.oracle_check(net, lanes, vals, N_LANE_WORDS)
        # timing lane: same IR object, vs the python oracle
        rec = analyze_ir(ir, arch)
        want = analyze_oracle(packed)
        timing_ok &= rec["critical_path_ps"] == want["critical_path_ps"]
        timing_ok &= rec["area_mwta"] == want["area_mwta"]
    counts = read_lower_counts()
    single_lowering = (counts["functional"] == len(nets)
                       and counts["placement_full"]
                       + counts["placement_incremental"] == len(nets))
    ok = bool(eval_ok and timing_ok and single_lowering)
    rec = {"oracle_match": ok, "eval_ok": bool(eval_ok),
           "timing_ok": bool(timing_ok),
           "single_lowering": bool(single_lowering),
           "lower_counts": counts, "n_circuits": len(nets)}
    if verbose:
        emit("ir_parity", 0,
             f"eval={eval_ok};timing={timing_ok};"
             f"single_lowering={single_lowering};counts={counts}")
    return rec


def main():
    rec = run()
    if not rec["oracle_match"]:
        raise AssertionError(f"IR parity failed: {rec}")
    return rec


if __name__ == "__main__":
    main()
