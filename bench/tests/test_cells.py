"""Runs of a throwaway benchmark tree on the CPU: each kind of traffic end
to end through the harness (the look for a chip skipped), and a
configuration, traffic mixes and a per-layer metric that are not in
``BENCHMARK.json`` found by name alone."""
import json
import os

import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_tree")))


@pytest.mark.parametrize("cell,metric", [
    ("tiny.eval", "lut_evals_per_s"),
    ("tiny.sweep", "records_per_s"),
])
def test_cell_runs_correct(root, cell, metric):
    res = tiny.run(root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert res["metrics"][metric]["value"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    json.dumps(res)


def test_traced_run_reads_the_added_metric(root):
    res = tiny.run(root, "tiny.eval", trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == {"tiny_calls_per_s", "eval_padded_rows"}
    assert res["metrics"]["eval_padded_rows"]["value"] >= 1.0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the traced run leaves no profile behind in the checkout
    assert not os.listdir(os.path.join(root, ".bench_trace"))


def test_unknown_workload_is_refused(root):
    from bench import harness

    with pytest.raises(harness.BenchError):
        tiny.run(root, "tiny.nothing")


@pytest.mark.parametrize("cell,number", [
    ("tiny.eval", "po_word_mismatches"),
    ("tiny.sweep", "record_mismatches"),
])
def test_control_is_not_correct(root, cell, number):
    """The control (the reference in float32 picoseconds and on 16-bit
    lane words, in the program's place) fails the cell's comparison, as
    ``bench/control.py`` reads it on the chip."""
    from bench import harness
    from bench.circuits import Designs

    spec = harness.load_spec(root)
    wl, cfg = harness.find_cell(spec, cell)
    traffic = harness.load_json(harness.traffic_path(root, wl["traffic"]))
    designs = Designs(harness.load_json(os.path.join(root, cfg["file"])))
    c = harness.load_kind(traffic["kind"]).Cell(
        designs=designs, traffic=traffic, seed=2**31 + 3, log=lambda m: None)
    c.setup(2.0)
    c.window(2.0, harness.Run(cell, traffic))
    c.release()
    program = {x.name: x for x in c.check()}
    control = {x.name: x for x in c.check(control=True)}
    assert program[number].ok
    assert not control[number].ok
