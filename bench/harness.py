"""The benchmark harness: one cell of ``BENCHMARK.json`` per process.

The harness is driven by data.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric lives in a file of
its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the deployment: which generators make
  its circuits, and the content digest of every netlist they must make;
* ``bench/traffic/<mix>.json`` — the traffic's parameters.  Its ``kind``
  names the general generator that reads it, ``bench/kinds/<kind>.py``;
* ``bench/metrics/<metric>.py`` — a reader, ``read(run)``, that takes one
  per-layer metric from the run's spans, counters and trace and returns
  ``None`` where it finds nothing to read;
* ``bench/peaks.json`` — device peaks keyed by ``device_kind``.

A run is: set-up (import, circuits, warm-up of every shape the window
uses), the window of ``--seconds`` (traced with ``--trace 1``), the device
memory peak, and then, with the program's state freed, the comparison of
a sample of the window's answers with the plain references in
``bench/reference``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared, beside its limit.  The same checks are the last lines of
standard error.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``--trace 1`` writes the profile here (inside the checkout), one
#: directory per workload, emptied before each traced run
TRACE_DIR = ".bench_trace"
#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so every run of a cell in one checkout finds the programs of the first
CACHE_DIR = ".jax_cache"


class BenchError(RuntimeError):
    """A run that must end without a result line."""


# ---------------------------------------------------------------------------
# the specification files
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def find_cell(spec: dict, workload: str) -> tuple[dict, dict]:
    """The workload entry and its configuration entry."""
    for wl in spec["workloads"]:
        if wl["name"] == workload:
            break
    else:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    for cfg in spec["configs"]:
        if cfg["name"] == wl["config"]:
            return wl, cfg
    raise BenchError(f"workload {workload!r} names no known config "
                     f"{wl['config']!r}")


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")


def load_kind(kind: str):
    """The general generator of a traffic kind: ``bench.kinds.<kind>``."""
    return importlib.import_module(f"bench.kinds.{kind}")


def load_metric(root: str, name: str):
    """The reader of one per-layer metric, ``bench/metrics/<name>.py``
    (loaded by path: metric names may hold dots)."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reports(entry: dict, workload: str) -> bool:
    ws = entry.get("workloads")
    return ws is None or workload in ws


def cell_metrics(spec: dict, workload: str) -> tuple[list, list]:
    """The end-to-end metric entries this cell reports, and the per-layer
    ones whose ``moves`` it reports."""
    e2e = [m for m in spec["end_to_end"] if reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if reports(m, workload) and m["moves"] in names]
    return e2e, per_layer


def device_peaks(root: str, device_kind: str) -> dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the process
# ---------------------------------------------------------------------------


def set_cache_env(root: str) -> str:
    """Point JAX's persistent compilation cache at the checkout.  Must run
    before JAX is imported; overrides a cache directory set outside, so
    two checkouts never share compiled programs."""
    path = os.path.join(root, CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return path


class CompileCounter:
    """Backend compiles and persistent-cache hits, counted by JAX's own
    monitoring events.  JAX offers no way to remove a listener, so the
    listeners are registered once per process and feed whichever counter
    is current."""

    _current: "CompileCounter | None" = None
    _registered = False

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        CompileCounter._current = self
        if not CompileCounter._registered:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
            CompileCounter._registered = True

    @staticmethod
    def _on_duration(event, duration, **kw):
        c = CompileCounter._current
        if c is not None and event == \
                "/jax/core/compile/backend_compile_duration":
            c.compiles += 1

    @staticmethod
    def _on_event(event, **kw):
        c = CompileCounter._current
        if c is not None and event == "/jax/compilation_cache/cache_hits":
            c.cache_hits += 1


@dataclass
class Run:
    """What one run hands to the per-layer metric readers."""

    workload: str
    traffic: dict
    window_s: float = 0.0
    #: host seconds per named span of the window (the benchmark's own
    #: spans around calls into layers, and the program's walls)
    spans: dict = field(default_factory=dict)
    #: counts of the window (compiles, rows, requests, batches, ...)
    counters: dict = field(default_factory=dict)
    #: the reduced profiler trace (:class:`bench.trace.Trace`), or None
    trace: object = None
    #: the device's row of ``bench/peaks.json``
    peaks: dict = field(default_factory=dict)
    n_chips: int = 1


@dataclass
class Check:
    """One number compared with the reference, beside its limit: the run
    is correct only where ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — a backend without the stats
            stats = None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(root: str, spec: dict, workload: str, seed: int,
             seconds: float, trace: bool, t0: float, devices,
             log=print) -> dict:
    """Set up, measure, check: everything of a run after the look for a
    chip.  Returns the result object (without printing it)."""
    from bench import circuits

    wl, cfg_entry = find_cell(spec, workload)
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(traffic_path(root, wl["traffic"]))
    kind = load_kind(traffic["kind"])
    e2e, per_layer = cell_metrics(spec, workload)
    n_chips = int(wl["chips"])
    used = devices[:n_chips]
    dev = used[0]
    counter = CompileCounter()
    run = Run(workload=workload, traffic=traffic, n_chips=n_chips)
    window = seconds
    if trace:
        from bench import trace as tr

        run.peaks = device_peaks(root, dev.device_kind)
        window = min(seconds, float(traffic.get("trace_seconds", seconds)))

    designs = circuits.Designs(config)
    cell = kind.Cell(designs=designs, traffic=traffic, seed=seed, log=log)
    cell.setup(window)
    setup_s = time.perf_counter() - t0
    log(f"bench: set-up {setup_s:.3f} s, compiles "
        f"{counter.compiles}, cache hits {counter.cache_hits}")
    compiles = counter.compiles
    if trace:
        tracer = tr.Capture(os.path.join(root, TRACE_DIR, workload))
        tracer.start()
    out = cell.window(window, run)
    if trace:
        tracer.stop()
    run.counters["window_compiles"] = counter.compiles - compiles
    run.window_s = out["window_s"]
    peak = memory_peak(used)

    cell.release()
    gc.collect()

    metrics: dict = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    if trace:
        run.trace = tr.Trace.load(tracer.path(), window_s=tracer.wall_s,
                                  n_chips=n_chips)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        for m in per_layer:
            value = load_metric(root, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        tracer.cleanup()
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    t_check = time.perf_counter()
    checks = cell.check()
    log(f"bench: reference check {time.perf_counter() - t_check:.3f} s")
    correct = (bool(checks) and all(c.ok for c in checks)
               and out["attempted"] > 0 and out["failed"] == 0)
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell on this machine's chips.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    try:
        spec = load_spec(ROOT)
        wl, _ = find_cell(spec, args.workload)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    set_cache_env(ROOT)
    from bench.chip import tpu_devices

    devices = tpu_devices(int(wl["chips"]))
    if devices is None:
        return 3

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    log(f"bench: device platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} count={len(devices)}")
    try:
        result = run_cell(ROOT, spec, args.workload, args.seed,
                          args.seconds, bool(args.trace), t0, devices,
                          log=log)
    except BenchError as e:
        log(f"bench: {e}")
        return 4
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
