"""The readers of the program's spans: synthetic ``repro.*`` rows through
the parsing step (no chip, no profile written), and traced runs of a
throwaway tree on the CPU."""
import json
import os
import shutil

import pytest

from bench import harness, program_spans
from bench.program_spans import Spans
from bench.tests import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST = "/host:CPU"
MS = 1_000_000      # nanoseconds

#: a window of 10 s: two eval calls, two sweep re-clusterings and lowers,
#: a device plane whose events are no program span
ROWS = [
    (HOST, "main", "repro.eval.call", 0, 4000 * MS, {"circuits": 4}),
    (HOST, "main", "repro.eval.plan", 0, 500 * MS, {}),
    (HOST, "main", "repro.eval.fill", 500 * MS, 300 * MS, {"bytes": 64}),
    (HOST, "main", "repro.eval.put", 800 * MS, 200 * MS, {"bytes": 64}),
    (HOST, "main", "repro.eval.run", 1000 * MS, 100 * MS, {}),
    (HOST, "main", "repro.eval.get", 1100 * MS, 400 * MS, {"bytes": 64}),
    (HOST, "main", "repro.eval.plan", 5000 * MS, 700 * MS, {}),
    (HOST, "main", "repro.eval.fill", 5700 * MS, 100 * MS, {"bytes": 64}),
    (HOST, "main", "repro.eval.put", 5800 * MS, 300 * MS, {"bytes": 64}),
    (HOST, "main", "repro.eval.get", 6200 * MS, 100 * MS, {"bytes": 64}),
    (HOST, "main", "repro.pack.cluster", 0, 2000 * MS,
     {"atoms": 10, "host_probes": 40, "hosted": 10, "unhosted": 4,
      "rej_mask": 3}),
    (HOST, "main", "repro.pack.cluster", 3000 * MS, 1000 * MS,
     {"atoms": 10, "host_probes": 60, "hosted": 15, "unhosted": 5}),
    (HOST, "main", "repro.ir.lower", 7000 * MS, 1000 * MS,
     {"incremental": 0}),
    (HOST, "main", "repro.ir.lower", 9000 * MS, 500 * MS,
     {"incremental": 1}),
    (HOST, "main", "bench.eval.call", 0, 4000 * MS, {}),
    ("/device:TPU:0", "XLA Ops", "repro.eval.plan", 0, 9000 * MS, {}),
]

READINGS = {
    "eval_plan_share": 100.0 * (0.5 + 0.7) / 10.0,
    "eval_fill_share": 100.0 * (0.3 + 0.1) / 10.0,
    "eval_transfer_share": 100.0 * (0.2 + 0.4 + 0.3 + 0.1) / 10.0,
    "lower_share.sweep": 100.0 * 1.5 / 10.0,
    "recluster_host_yield.sweep": 100.0 * (25 - 9) / 100,
}


@pytest.fixture()
def tree(tmp_path, monkeypatch):
    """A checkout holding the benchmark's readers and one (empty) profile
    file per cell; parsing a profile yields :data:`ROWS` by default."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    for wl in ("cell.eval", "cell.sweep"):
        d = os.path.join(root, harness.TRACE_DIR, wl, "plugins", "profile",
                         "run")
        os.makedirs(d)
        open(os.path.join(d, "host.xplane.pb"), "wb").close()
    parsed = {"rows": ROWS}
    monkeypatch.setattr(program_spans, "rows_of",
                        lambda path: iter(parsed["rows"]))
    monkeypatch.setattr(program_spans, "_PARSED", {})
    return root, parsed


def _run(workload, trace=True, window_s=10.0):
    return harness.Run(workload=workload, traffic={}, window_s=window_s,
                       trace=object() if trace else None)


def _cell(metric):
    return "cell.sweep" if metric.endswith(".sweep") else "cell.eval"


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_gives_its_ratio(tree, metric):
    root, _ = tree
    reader = harness.load_metric(root, metric)
    assert reader.read(_run(_cell(metric))) == pytest.approx(
        READINGS[metric], rel=1e-12)


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_gives_none_without_a_span(tree, metric):
    """A program without spans (an earlier commit) reports nothing."""
    root, parsed = tree
    parsed["rows"] = [r for r in ROWS if not r[2].startswith("repro.")]
    assert harness.load_metric(root, metric).read(_run(_cell(metric))) \
        is None


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_gives_none_without_a_profile(tree, metric):
    root, _ = tree
    reader = harness.load_metric(root, metric)
    assert reader.read(_run(_cell(metric), trace=False)) is None
    shutil.rmtree(os.path.join(root, harness.TRACE_DIR))
    assert reader.read(_run(_cell(metric))) is None


def test_host_yield_needs_probes(tree):
    root, parsed = tree
    parsed["rows"] = [(HOST, "main", "repro.pack.cluster", 0, MS,
                       {"atoms": 3, "host_probes": 0, "hosted": 0,
                        "unhosted": 0})]
    reader = harness.load_metric(root, "recluster_host_yield.sweep")
    assert reader.read(_run("cell.sweep")) is None


def test_host_yield_drops_with_undone_hostings(tree):
    """A hosting taken back counts as a probe spent for nothing."""
    root, parsed = tree
    reader = harness.load_metric(root, "recluster_host_yield.sweep")
    readings = []
    for unhosted in (0, 2):
        parsed["rows"] = [(HOST, "main", "repro.pack.cluster", 0, MS,
                           {"host_probes": 10, "hosted": 4,
                            "unhosted": unhosted})]
        program_spans._PARSED.clear()
        readings.append(reader.read(_run("cell.sweep")))
    assert readings == [pytest.approx(40.0), pytest.approx(20.0)]


def test_nested_spans_count_once():
    """A span inside another span of the same reading, or overlapping
    it, adds only the time it adds to their union."""
    rows = [(HOST, "t1", "repro.ir.lower", 0, 10 * MS, {}),
            (HOST, "t1", "repro.ir.lower", 2 * MS, 3 * MS, {}),
            (HOST, "t2", "repro.eval.put", 20 * MS, 10 * MS, {}),
            (HOST, "t2", "repro.eval.get", 25 * MS, 10 * MS, {}),
            (HOST, "t2", "repro.eval.get", 40 * MS, 5 * MS, {})]
    sp = Spans.from_rows(rows)
    assert sp.seconds("repro.ir.lower") == pytest.approx(0.010)
    assert sp.seconds("repro.eval.put", "repro.eval.get") == \
        pytest.approx(0.020)
    assert sp.seconds("repro.eval.plan") is None


def test_stats_are_kept_and_summed():
    sp = Spans.from_rows(ROWS)
    assert sp.stat_sum("repro.pack.cluster", "host_probes") == 100
    assert sp.stat_sum("repro.pack.cluster", "rej_mask") == 3
    assert sp.stat_sum("repro.pack.cluster", "rej_zbud") is None
    assert sp.stat_sum("repro.eval.fill", "bytes") == 128
    assert "bench.eval.call" not in sp.by_name


def test_one_parse_per_profile(tree, monkeypatch):
    root, _ = tree
    calls = []
    real = program_spans.rows_of
    monkeypatch.setattr(program_spans, "rows_of",
                        lambda path: calls.append(path) or real(path))
    for metric in READINGS:
        harness.load_metric(root, metric).read(_run("cell.eval"))
    assert len(calls) == 1


NEW_METRICS = [
    ("eval_plan_share", "eval program and planner (eval_jax.py, plan.py)",
     "lut_evals_per_s", "tiny.eval"),
    ("eval_fill_share", "host value buffers (eval_jax.py SuiteProgram.run)",
     "lut_evals_per_s", "tiny.eval"),
    ("eval_transfer_share",
     "host value buffers (eval_jax.py SuiteProgram.run)",
     "lut_evals_per_s", "tiny.eval"),
    ("lower_share.sweep", "lower", "records_per_s", "tiny.sweep"),
    ("recluster_host_yield.sweep", "pack (packing.py, repack.py)",
     "records_per_s", "tiny.sweep"),
]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_tree")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"] += [
        {"name": n, "unit": "%", "better": "lower",
         "source": "program_span", "layer": layer, "moves": moves,
         "workloads": [wl]} for n, layer, moves, wl in NEW_METRICS]
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.mark.parametrize("cell", ["tiny.eval", "tiny.sweep"])
def test_traced_run_reads_the_program_spans(tiny_root, cell):
    res = tiny.run(tiny_root, cell, trace=True)
    assert res["correct"]
    want = {n for n, _, _, wl in NEW_METRICS if wl == cell}
    got = {n: m["value"] for n, m in res["metrics"].items() if n in want}
    assert set(got) == want
    for name, value in got.items():
        assert 0.0 < value <= 100.0, (name, value)
