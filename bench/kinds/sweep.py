"""Architecture-sweep traffic: ``flow.sweep_architectures`` over one
structural class of an architecture grid per call, as a design-space
study issues it.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``grid`` — keyword arguments of ``alm.arch_grid``: the grid swept;
* ``order`` — the classes in the order the window sweeps them, each
  named by its first grid point; the window cycles through the list;
* ``warmup_grid`` / ``warmup`` — a grid outside the window's sample and
  the classes of it that set-up sweeps: one per delay-row count the
  window's classes have, so every timing-program shape is compiled;
* ``pack_seed`` — the packing seed of every call;
* ``backend`` — the timing backend (``"jax"``: the device program).

The reference re-packs and re-times, for every circuit, one of the
window's calls drawn from ``--seed``: every record of that circuit in
that call.

``records_per_s`` is (circuit, grid point) area+delay records completed,
over the time from the window's start to the last call's completion: the
window issues calls until its deadline has passed.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.harness import BenchError, Check
from bench.reference import alm as ref_alm
from bench.reference import packing as ref_packing
from bench.reference import timing as ref_timing
from bench.reference.netlist import from_fields

#: the record fields a sweep reports and the reference re-derives
FIELDS = ("critical_path_ps", "area_mwta", "alms", "lbs", "adp",
          "concurrent_luts")

#: program wall keys of ``SweepResult.wall`` summed over the window
WALLS = ("prefix_s", "recluster_s", "lower_s", "build_s", "timing_s")


def _classes(alm, grid_kwargs: dict):
    grid = alm.arch_grid(**grid_kwargs)
    classes = alm.group_archs_by_structure(grid)
    return {grid[idx[0]].name: [grid[i] for i in idx] for idx in classes}


class Cell:
    def __init__(self, designs, traffic: dict, seed: int, log=print):
        self.designs = designs
        self.traffic = traffic
        self.seed = seed
        self.log = log
        self.pack_seed = int(traffic["pack_seed"])
        self.done: list[tuple[str, list[str], list[list[dict]]]] = []

    def _sweep(self, archs):
        from repro.core import flow

        return flow.sweep_architectures(
            self.suites, archs=archs, seed=self.pack_seed,
            backend=self.traffic["backend"])

    def setup(self, seconds: float) -> None:
        from repro.core import alm

        self.suites = self.designs.suites()
        self.nets = [n for ns in self.suites.values() for n in ns]
        by_name = _classes(alm, self.traffic["grid"])
        missing = [c for c in self.traffic["order"] if c not in by_name]
        if missing or len(set(self.traffic["order"])) != len(by_name):
            raise BenchError(f"sweep order {self.traffic['order']} does not "
                             f"name each class of the grid once "
                             f"({sorted(by_name)})")
        self.order = [(c, by_name[c]) for c in self.traffic["order"]]
        warm = _classes(alm, self.traffic["warmup_grid"])
        for name in self.traffic["warmup"]:
            t0 = time.perf_counter()
            self._sweep(warm[name])
            self.log(f"sweep: warm-up class {name} ({len(warm[name])} rows)"
                     f" {time.perf_counter() - t0:.3f} s")

    def window(self, seconds: float, run) -> dict:
        walls = dict.fromkeys(WALLS, 0.0)
        records = calls = 0
        t_start = time.perf_counter()
        deadline = t_start + seconds
        t_end = t_start
        while time.perf_counter() < deadline:
            name, archs = self.order[calls % len(self.order)]
            t_call = time.perf_counter()
            with TraceAnnotation(f"bench.sweep.{name}"):
                res = self._sweep(archs)
            t_end = time.perf_counter()
            self.log(f"sweep: class {name} ({len(archs)} rows) "
                     f"{t_end - t_call:.3f} s")
            for k in WALLS:
                walls[k] += res.wall.get(k, 0.0)
            records += len(self.nets) * len(archs)
            self.done.append((name, [a.name for a in archs], res.records))
            calls += 1
        elapsed = max(t_end - t_start, 1e-9)
        run.spans.update(walls)
        run.counters.update(sweep_calls=calls, sweep_records=records)
        return {"window_s": elapsed, "attempted": records, "failed": 0,
                "metrics": {"records_per_s": records / elapsed}}

    def release(self) -> None:
        self.ref_nets = [from_fields(n) for n in self.nets]
        self.suites = self.nets = None

    def check(self, control: bool = False) -> list[Check]:
        """Re-pack and re-time, for every circuit, one of the window's
        calls drawn from the seed, with the plain references; every
        record field must equal the reference's.  With ``control`` the
        reference's timing in float32 picoseconds stands in for the
        program."""
        rng = np.random.default_rng([self.seed % 2**64, 1])
        pick = ([(int(rng.integers(len(self.done))), g)
                 for g in range(len(self.ref_nets))] if self.done else [])
        ref_grid = {a.name: a for a in ref_alm.arch_grid(
            **self.traffic["grid"])}
        bad = checked = 0
        for d, g in pick:
            name, arch_names, records = self.done[d]
            ref_net = self.ref_nets[g]
            packed = ref_packing.pack(ref_net, ref_grid[arch_names[0]],
                                      seed=self.pack_seed)
            for k, an in enumerate(arch_names):
                packed.arch = ref_grid[an]
                want = ref_timing.analyze_oracle(packed)
                got = (ref_timing.analyze_oracle(packed, dtype=np.float32)
                       if control else records[g][k])
                checked += 1
                if any(got[f] != want[f] for f in FIELDS):
                    bad += 1
        self.log(f"sweep: checked {checked} records of {len(pick)} "
                 f"circuits, each in one call")
        return [Check("record_mismatches", bad, 0),
                Check("records_unchecked", 0 if checked else 1, 0)]
