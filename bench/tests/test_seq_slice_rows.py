"""``seq_slice_rows.stream``: the share of value-buffer rows the cycle
program writes by slice, from the ``repro.seq.run`` spans' stats —
synthetic rows through the parsing step, and a traced run of the stream
traffic on a small systolic array on the CPU."""
import json
import os
import shutil

import pytest

from bench import harness, program_spans
from bench.tests import test_stream

METRIC = "seq_slice_rows.stream"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST = "/host:CPU"
MS = 1_000_000


def _rows(*stats):
    return [(HOST, "main", "repro.seq.run", i * 10 * MS, 5 * MS, st)
            for i, st in enumerate(stats)]


@pytest.fixture()
def tree(tmp_path, monkeypatch):
    """A checkout holding the readers and one (empty) profile file."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    d = os.path.join(root, harness.TRACE_DIR, "cell.stream", "plugins",
                     "profile", "run")
    os.makedirs(d)
    open(os.path.join(d, "host.xplane.pb"), "wb").close()
    parsed = {"rows": []}
    monkeypatch.setattr(program_spans, "rows_of",
                        lambda path: iter(parsed["rows"]))
    monkeypatch.setattr(program_spans, "_PARSED", {})
    return root, parsed


def _read(root, trace=True):
    run = harness.Run(workload="cell.stream", traffic={}, window_s=1.0,
                      trace=object() if trace else None)
    return harness.load_metric(root, METRIC).read(run)


@pytest.mark.parametrize("stats,want", [
    ([{"slice_rows": 300, "scatter_rows": 0}] * 2, 100.0),
    ([{"slice_rows": 300, "scatter_rows": 100},
      {"slice_rows": 100, "scatter_rows": 0}], 80.0),
    ([{"slice_rows": 0, "scatter_rows": 50}], 0.0),
    ([{}, {}], None),                    # a span without the stats
    ([{"slice_rows": 0, "scatter_rows": 0}], None),
    ([], None),                          # no span at all
])
def test_reader_gives_the_sliced_share(tree, stats, want):
    root, parsed = tree
    parsed["rows"] = _rows(*stats)
    got = _read(root)
    assert got == (None if want is None else pytest.approx(want))


def test_reader_gives_none_without_a_profile(tree):
    root, parsed = tree
    parsed["rows"] = _rows({"slice_rows": 1, "scatter_rows": 0})
    assert _read(root, trace=False) is None
    shutil.rmtree(os.path.join(root, harness.TRACE_DIR))
    assert _read(root) is None


@pytest.fixture()
def stream_root(tmp_path):
    from repro.core import plan

    root = test_stream._make_root(str(tmp_path), test_stream._config())
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append(
        {"name": METRIC, "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "lut_evals_per_s"})
    with open(path, "w") as f:
        json.dump(spec, f)
    plan.clear_caches()
    yield root
    plan.clear_caches()


def test_traced_stream_run_writes_every_row_by_slice(stream_root):
    res = test_stream._run(stream_root, trace=True)
    assert res["correct"]
    assert res["metrics"][METRIC]["value"] == pytest.approx(100.0)
