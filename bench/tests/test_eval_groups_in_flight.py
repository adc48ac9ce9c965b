"""``eval_groups_in_flight``: the envelope groups dispatched and not yet
fetched when the eval program fetches a result, from the
``repro.eval.get`` spans' ``queued`` stat — synthetic rows through the
parsing step, and a traced run of the tiny eval traffic on the CPU."""
import json
import os
import shutil

import pytest

from bench import harness, program_spans
from bench.tests import tiny

METRIC = "eval_groups_in_flight"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST = "/host:CPU"
MS = 1_000_000


def _rows(*stats):
    return [(HOST, "main", "repro.eval.get", i * 10 * MS, 5 * MS, st)
            for i, st in enumerate(stats)]


@pytest.fixture()
def tree(tmp_path, monkeypatch):
    """A checkout holding the readers and one (empty) profile file."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    d = os.path.join(root, harness.TRACE_DIR, "cell.eval", "plugins",
                     "profile", "run")
    os.makedirs(d)
    open(os.path.join(d, "host.xplane.pb"), "wb").close()
    parsed = {"rows": []}
    monkeypatch.setattr(program_spans, "rows_of",
                        lambda path: iter(parsed["rows"]))
    monkeypatch.setattr(program_spans, "_PARSED", {})
    return root, parsed


def _read(root, trace=True):
    run = harness.Run(workload="cell.eval", traffic={}, window_s=1.0,
                      trace=object() if trace else None)
    return harness.load_metric(root, METRIC).read(run)


@pytest.mark.parametrize("stats,want", [
    # two calls of four groups, every group dispatched before any get
    ([{"bytes": 8, "queued": q} for q in (4, 3, 2, 1)] * 2, 2.5),
    # each group fetched before the next is dispatched
    ([{"bytes": 8, "queued": 1}] * 3, 1.0),
    ([{"bytes": 8, "queued": 2}, {"bytes": 8, "queued": 1},
      {"bytes": 8, "queued": 1}], 4 / 3),
    ([{"bytes": 8}, {"bytes": 8}], None),     # gets without the stat
    ([], None),                               # no get at all
])
def test_reader_gives_the_mean_queued(tree, stats, want):
    root, parsed = tree
    parsed["rows"] = _rows(*stats)
    got = _read(root)
    assert got == (None if want is None else pytest.approx(want))


def test_reader_gives_none_without_a_profile(tree):
    root, parsed = tree
    parsed["rows"] = _rows({"bytes": 8, "queued": 1})
    assert _read(root, trace=False) is None
    shutil.rmtree(os.path.join(root, harness.TRACE_DIR))
    assert _read(root) is None


@pytest.fixture()
def eval_root(tmp_path):
    from repro.core import plan

    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append(
        {"name": METRIC, "unit": "groups", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "lut_evals_per_s", "workloads": ["tiny.eval"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    plan.clear_caches()
    yield root
    plan.clear_caches()


def test_traced_eval_run_dispatches_both_groups_first(eval_root):
    """The tiny configuration's two circuits are two envelope groups: each
    call's gets carry ``queued`` 2, then 1."""
    res = tiny.run(eval_root, "tiny.eval", trace=True)
    assert res["correct"]
    assert res["metrics"][METRIC]["value"] == pytest.approx(1.5)
