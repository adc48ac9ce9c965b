"""The flow's spans: a span adds its seconds to a program wall, and under a
``jax.profiler`` capture the layer spans of evaluation, sweeping and
packing land in the profile with their stats."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import flow
from repro.core.alm import BASELINE, DD5
from repro.core.circuits import kratos_gemm, vtr_mixed
from repro.core.eval_jax import eval_netlist_jax
from repro.core.packing import HOST_COUNTERS, pack
from repro.core.plan import clear_caches
from repro.core.spans import span
from repro.core.sweep import oracle_parity, sweep_suite


def test_span_adds_its_seconds_to_the_wall():
    wall = {"a_s": 1.0}
    with span("repro.test", wall, "a_s", size=3):
        pass
    with span("repro.test", wall, "b_s") as sp:
        sp.set(count=2)
    assert wall["a_s"] > 1.0 and wall["b_s"] > 0.0
    assert set(wall) == {"a_s", "b_s"}


def test_span_with_a_wall_needs_a_key():
    with pytest.raises(ValueError):
        span("repro.test", {})


def _repro_events(log_dir):
    """``(name, stats)`` of every ``repro.*`` event of the capture, the
    event's seconds in ``stats["_s"]``."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    rows = [(e.start_ns, e.name, dict(e.stats, _s=e.duration_ns / 1e9))
            for plane in data.planes for line in plane.lines
            for e in line.events if e.name.startswith("repro.")]
    return [(name, stats) for _, name, stats in sorted(
        rows, key=lambda r: r[0])]


def _lanes(net, n_words, seed):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, 2**32, n_words, dtype=np.uint32)
            for s in net.pis}


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One profile of a tiny suite evaluation (the suite entry and the
    single-circuit entry), a tiny two-class sweep and a baseline and a DD5
    pack of an adder circuit, with the outputs each produced.  Every
    program compiles before the capture starts: a capture that holds
    compiles takes minutes to write."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    nets = [vtr_mixed(name="t0", n_in=10, logic_nodes=30, adders=2,
                      add_width=6, seed=0)]
    lanes = [_lanes(n, 4, i) for i, n in enumerate(nets)]
    gemm = kratos_gemm(name="g", m=4, n=4, width=4)

    def evaluate():
        outs, stats = flow.evaluate_suite(nets, lanes, 4, use_pallas=False,
                                          max_buckets=1)
        return {"suite": (outs, stats),
                "single": ([eval_netlist_jax(n, ln, 4, use_pallas=False)
                            for n, ln in zip(nets, lanes)], None)}

    def sweep():
        return sweep_suite([gemm], [BASELINE, DD5], backend="jax")

    evaluate()
    sweep()
    jax.profiler.start_trace(log_dir)
    try:
        outs = evaluate()
        # fresh prefixes, packs and IR templates; the timing program's
        # compiled executable is shared by shape and stays
        clear_caches()
        res = sweep()
        packs = {a.name: pack(gemm, a) for a in (BASELINE, DD5)}
    finally:
        jax.profiler.stop_trace()
    return {"events": _repro_events(log_dir), "nets": nets, "lanes": lanes,
            "outs": outs, "gemm": gemm, "sweep": res, "packs": packs}


def _stats(captured, name):
    return [st for n, st in captured["events"] if n == name]


@pytest.mark.parametrize("name,keys", [
    ("repro.eval.call", {"circuits", "lane_words"}),
    ("repro.eval.plan", set()),
    ("repro.eval.fill", {"bytes"}),
    ("repro.eval.put", {"bytes"}),
    ("repro.eval.run", set()),
    ("repro.eval.get", {"bytes"}),
    ("repro.pack.cluster", {"atoms", "lbs", *HOST_COUNTERS}),
    ("repro.ir.lower", {"incremental"}),
    ("repro.timing.build", {"groups"}),
    ("repro.timing.run", {"rows"}),
])
def test_spans_land_in_the_profile_with_their_stats(captured, name, keys):
    stats = _stats(captured, name)
    assert stats, f"no {name} event"
    for st in stats:
        assert set(st) == keys | {"_s"}, (name, st)


def test_eval_spans_count_the_call(captured):
    nets = captured["nets"]
    (call,) = _stats(captured, "repro.eval.call")
    assert call["circuits"] == 1 and call["lane_words"] == 4
    moved = {name: sum(st["bytes"] for st in _stats(captured, name))
             for name in ("repro.eval.fill", "repro.eval.put",
                          "repro.eval.get")}
    # both entries send only the primary inputs' lanes, 4 words of 4
    # bytes each (one circuit per group, so no padded PI slot), and get
    # back every signal
    assert moved["repro.eval.put"] == moved["repro.eval.fill"] \
        == 2 * sum(len(n.pis) for n in nets) * 16
    assert moved["repro.eval.get"] >= 2 * sum(n.n_signals for n in nets) * 16


def test_spans_change_no_output(captured):
    nets, lanes = captured["nets"], captured["lanes"]
    for entry, (outs, stats) in captured["outs"].items():
        if entry == "suite":
            assert stats["mode"] == "grouped"
        for net, ln, vals in zip(nets, lanes, outs):
            assert flow.oracle_check(net, ln, vals, 4)
    assert oracle_parity(captured["sweep"], [captured["gemm"]],
                         [BASELINE, DD5])


def test_cluster_counters(captured):
    """Baseline then DD5, in the sweep and in the plain packs: the
    baseline never probes for a host; DD5 hosts LUTs in adder ALMs, and
    takes back the first half of a split pair whose second half found no
    ALM, which lowers the share of probes that leave a LUT hosted."""
    clusters = _stats(captured, "repro.pack.cluster")
    assert len(clusters) == 4
    packs = captured["packs"]
    assert packs["dd5"].concurrent_luts > 0
    for st, arch in zip(clusters, ("baseline", "dd5") * 2):
        assert 0 <= st["unhosted"] <= st["hosted"] <= st["host_probes"]
        assert all(st[k] >= 0 for k in HOST_COUNTERS)
        assert st["lbs"] == len(packs[arch].lbs)
        if arch == "baseline":
            assert st["host_probes"] == 0
        else:
            assert st["hosted"] > 0 and st["unhosted"] > 0
            kept = st["hosted"] - st["unhosted"]
            assert kept / st["host_probes"] < st["hosted"] / st["host_probes"]


def _spans_match_the_wall(captured, name, key):
    spans = _stats(captured, name)
    assert len(spans) == 2      # one per class, or per lowering
    wall = captured["sweep"].wall[key]
    assert wall > 0.0
    assert abs(sum(st["_s"] for st in spans) - wall) <= 0.05 * wall + 1e-3
    return spans


def test_lower_spans_add_up_to_the_wall(captured):
    """``wall["lower_s"]`` is the sum of the lowering spans: one
    measurement, on two clocks."""
    lowers = _spans_match_the_wall(captured, "repro.ir.lower", "lower_s")
    assert [st["incremental"] for st in lowers] == [0, 1]


@pytest.mark.parametrize("name,key", [("repro.timing.build", "build_s"),
                                      ("repro.timing.run", "timing_s")])
def test_timing_spans_add_up_to_the_wall(captured, name, key):
    """The timing program's wall keys are the sums of its spans."""
    _spans_match_the_wall(captured, name, key)
