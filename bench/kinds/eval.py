"""Functional simulation traffic: ``flow.evaluate_suite`` over a
configuration's circuits, call after call, each call on fresh random test
vectors.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``lane_words`` — uint32 words of test vectors per primary input and
  call (32 vectors a word);
* ``use_pallas`` — evaluate LUT levels with the ``lut_eval6`` kernel;
* ``check_calls`` — how many of the window's calls the reference
  re-evaluates, every lane word of each (drawn from the seed);
* ``trace_seconds`` — the traced window of a ``--trace 1`` run.

Call ``i`` of a run evaluates the vectors drawn from ``(seed, i)``; the
warm-up call uses vectors no window call uses.  ``lut_evals_per_s`` is
the sum over the window's calls of real LUTs x lane words x 32, divided
by the time from the window's start to the last call's completion.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.harness import Check
from bench.reference.lanes import (block_lanes, count_mismatches,
                                   reference_pos)
from bench.reference.netlist import from_fields


def _entropy(seed: int, call: int) -> list[int]:
    return [seed % 2**64, call % 2**64]


class Cell:
    def __init__(self, designs, traffic: dict, seed: int, log=print):
        self.designs = designs
        self.traffic = traffic
        self.seed = seed
        self.log = log
        self.n_words = int(traffic["lane_words"])
        self.use_pallas = bool(traffic.get("use_pallas", True))
        self.kept: list[tuple[int, list]] = []

    def _lanes(self, call: int) -> list[dict]:
        return [block_lanes(n, self.n_words, _entropy(self.seed, call)
                            + [i]) for i, n in enumerate(self.nets)]

    def _call(self, lanes):
        from repro.core import flow

        return flow.evaluate_suite(self.nets, lanes, self.n_words,
                                   use_pallas=self.use_pallas)

    def setup(self, seconds: float) -> None:
        self.nets = self.designs.circuits()
        self.real_luts = sum(n.n_luts for n in self.nets)
        self.po_sigs = [np.array([s for bus in n.pos.values() for s in bus],
                                 dtype=np.int64) for n in self.nets]
        # the warm-up call compiles every program the window runs: the
        # window's calls differ from it only in their vectors
        _, stats = self._call(self._lanes(2**63))
        self.log(f"eval: warm-up {stats['mode']} with {stats['n_groups']} "
                 f"programs, {self.real_luts} LUTs x {self.n_words} words")

    def window(self, seconds: float, run) -> dict:
        rng = np.random.default_rng(_entropy(self.seed, 2**62))
        k = int(self.traffic["check_calls"])
        t_lanes = t_eval = 0.0
        calls = 0
        stats = None
        t_start = time.perf_counter()
        deadline = t_start + seconds
        t_end = t_start
        while time.perf_counter() < deadline:
            t0 = time.perf_counter()
            with TraceAnnotation("bench.eval.lanes"):
                lanes = self._lanes(calls)
            t1 = time.perf_counter()
            with TraceAnnotation("bench.eval.call"):
                outs, stats = self._call(lanes)
            t_end = time.perf_counter()
            t_lanes += t1 - t0
            t_eval += t_end - t1
            # reservoir sample of the calls the reference re-evaluates
            slot = calls if calls < k else int(rng.integers(0, calls + 1))
            if slot < k:
                pos = [np.asarray(v)[po] for v, po in zip(outs, self.po_sigs)]
                entry = (calls, pos)
                if calls < k:
                    self.kept.append(entry)
                else:
                    self.kept[slot] = entry
            calls += 1
        elapsed = max(t_end - t_start, 1e-9)
        run.spans.update(eval_lanes_s=t_lanes, eval_calls_s=t_eval)
        run.counters.update(eval_calls=calls,
                            eval_real_lut_rows=self.real_luts,
                            eval_lane_words=self.n_words)
        if stats is not None:
            run.counters["eval_mode"] = stats["mode"]
            run.counters["eval_programs"] = stats["n_groups"]
            run.counters["eval_padded_lut_rows"] = self._padded_rows(stats)
        return {"window_s": elapsed, "attempted": calls, "failed": 0,
                "metrics": {"lut_evals_per_s": calls * self.real_luts
                            * self.n_words * 32 / elapsed}}

    def _padded_rows(self, stats) -> int:
        """LUT rows each call evaluates, padding included, as the
        evaluator's own plans and group stats count them."""
        if stats["mode"] == "grouped":
            return int(sum(g["padded_lut_rows"] for g in stats["groups"]))
        from repro.core.eval_jax import plan_netlist

        return int(sum(plan_netlist(n).padded_lut_rows for n in self.nets))

    def release(self) -> None:
        self.nets_fields = [(n.name, from_fields(n)) for n in self.nets]
        self.nets = None

    def check(self, control: bool = False) -> list[Check]:
        """Re-evaluate every lane word of the sampled calls with the
        plain evaluator; every primary-output word must match.  With
        ``control`` the reference on 16-bit words stands in for the
        program."""
        bad = words = 0
        for call, pos in self.kept:
            for i, (name, ref) in enumerate(self.nets_fields):
                lanes = block_lanes(ref, self.n_words,
                                    _entropy(self.seed, call) + [i])
                want = reference_pos(ref, lanes)
                if control:
                    got = reference_pos(ref, lanes, half_words=True)
                else:
                    got = {int(s): pos[i][r] for r, s in enumerate(
                        s for bus in ref.pos.values() for s in bus)}
                bad += count_mismatches(got, want)
                words += sum(len(w) for w in want.values())
        self.log(f"eval: checked {len(self.kept)} calls, {words} output "
                 f"words")
        return [Check("po_word_mismatches", bad, 0),
                Check("calls_unchecked", 0 if self.kept else 1, 0)]
