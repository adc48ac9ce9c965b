"""A throwaway benchmark tree for CPU tests: a tiny configuration, its own
traffic mixes and per-layer metrics, written into a temporary directory
beside copies of the benchmark's metric readers.  Nothing here is in
``BENCHMARK.json``; the harness finds it all by name, as it finds a
later PR's additions."""
from __future__ import annotations

import json
import os
import shutil
import time

from bench.reference.netlist import digest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the circuits of the tiny configuration: two general-logic circuits of
#: the VTR-like generator (about 40 LUTs each)
SUITE = {"suite": "tiny", "generator": "vtr_mixed",
         "kwargs": {"name": "tiny-logic", "n_in": 12, "logic_nodes": 40,
                    "adders": 2, "add_width": 6, "seed": 0}}
SUITE2 = {"suite": "tiny2", "generator": "vtr_mixed",
          "kwargs": {"name": "tiny-adders", "n_in": 8, "logic_nodes": 24,
                     "adders": 3, "add_width": 8, "seed": 1}}

TRAFFIC = {
    "tiny.eval": {"kind": "eval", "lane_words": 8, "use_pallas": False,
                  "check_calls": 3},
    "tiny.sweep": {"kind": "sweep",
                   "grid": {"bypass_inputs": [0, 2], "addmux_fanin": [10],
                            "lut6": [False]},
                   "order": ["b0", "b2_f10"],
                   "warmup_grid": {"bypass_inputs": [0],
                                   "direct_link_inputs": [20]},
                   "warmup": ["b0_d20"], "pack_seed": 0, "backend": "jax"},
}

#: a per-layer metric only this tree has
EXTRA_METRIC = '''"""Calls per second of the window (a throwaway test metric)."""


def read(run):
    calls = run.counters.get("eval_calls")
    return None if not calls else calls / run.window_s
'''


def config() -> dict:
    from repro.core.circuits import vtr_mixed

    suites = [dict(s, circuits={s["kwargs"]["name"]:
                                digest(vtr_mixed(**s["kwargs"]))})
              for s in (SUITE, SUITE2)]
    return {"name": "tiny", "suites": suites}


def make_root(path: str, cfg: dict | None = None) -> str:
    """Write the tree under ``path`` and return it."""
    os.makedirs(os.path.join(path, "bench", "configs"))
    os.makedirs(os.path.join(path, "bench", "traffic"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(path, "bench", "metrics"))
    with open(os.path.join(path, "bench", "metrics",
                           "tiny_calls_per_s.py"), "w") as f:
        f.write(EXTRA_METRIC)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = {"hbm_bytes_per_s": 1e10}
    with open(os.path.join(path, "bench", "peaks.json"), "w") as f:
        json.dump(peaks, f)
    with open(os.path.join(path, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg or config(), f)
    for name, t in TRAFFIC.items():
        with open(os.path.join(path, "bench", "traffic",
                               f"{name}.json"), "w") as f:
            json.dump(t, f)
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": f"tiny.{k}", "config": "tiny",
                       "traffic": f"tiny.{k}", "chips": 1, "why": "test"}
                      for k in ("eval", "sweep")],
        "end_to_end": [
            {"name": "records_per_s", "unit": "records/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.sweep"]},
            {"name": "lut_evals_per_s", "unit": "lut-vectors/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.eval"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "tiny_calls_per_s", "unit": "calls/s",
             "better": "higher", "source": "program_counter",
             "layer": "test", "moves": "lut_evals_per_s",
             "workloads": ["tiny.eval"]},
            {"name": "eval_padded_rows", "unit": "rows/row",
             "better": "lower", "source": "program_counter",
             "layer": "eval program and planner (eval_jax.py, plan.py)",
             "moves": "lut_evals_per_s", "workloads": ["tiny.eval"]},
            {"name": "recluster_share.sweep", "unit": "%",
             "better": "lower", "source": "program_span",
             "layer": "pack (packing.py, repack.py)",
             "moves": "records_per_s", "workloads": ["tiny.sweep"]}],
    }
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return path


def run(root: str, workload: str, seed: int = 2**31 + 11,
        seconds: float = 2.0, trace: bool = False) -> dict:
    """Everything of a run after the look for a chip, on this CPU."""
    import jax

    from bench import harness

    spec = harness.load_spec(root)
    return harness.run_cell(root, spec, workload, seed, seconds, trace,
                            time.perf_counter(), jax.devices(),
                            log=lambda msg: None)
