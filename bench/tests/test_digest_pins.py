"""A configuration's netlists are held to its pinned digests: a changed
netlist, a changed pin or a different circuit list ends the run."""
import copy

import pytest

from bench import circuits
from bench.tests import tiny


@pytest.fixture(scope="module")
def cfg():
    return tiny.config()


def test_pins_hold(cfg):
    d = circuits.Designs(cfg)
    assert [n.name for n in d.circuits()] == ["tiny-logic", "tiny-adders"]


def test_changed_netlist_is_refused(cfg, monkeypatch):
    from repro.core import circuits as gen

    real = gen.vtr_mixed

    def changed(*a, **kw):
        net = real(*a, **kw)
        net.lut_tt[0] ^= 1          # one truth-table bit differs
        return net

    monkeypatch.setattr(gen, "vtr_mixed", changed)
    with pytest.raises(circuits.DigestMismatch):
        circuits.Designs(cfg).circuits()


def test_wrong_pin_is_refused(cfg):
    bad = copy.deepcopy(cfg)
    bad["suites"][0]["circuits"]["tiny-logic"] = "0" * 32
    with pytest.raises(circuits.DigestMismatch):
        circuits.Designs(bad).suites()


def test_other_circuit_list_is_refused(cfg):
    bad = copy.deepcopy(cfg)
    bad["suites"][0]["kwargs"]["name"] = "tiny-renamed"
    with pytest.raises(circuits.DigestMismatch):
        circuits.Designs(bad).suites()


def test_refused_run_prints_no_result(cfg, tmp_path, monkeypatch):
    from bench import harness

    bad = copy.deepcopy(cfg)
    bad["suites"][1]["circuits"]["tiny-adders"] = "f" * 32
    root = tiny.make_root(str(tmp_path), cfg=bad)
    with pytest.raises(harness.BenchError):
        tiny.run(root, "tiny.sweep")


def test_benchmark_configs_pin_what_the_generators_make():
    """The committed configurations match today's generators, circuit
    for circuit, with the sizes their files state."""
    import json
    import os

    for name in sorted(os.listdir(os.path.join(tiny.BENCH, "configs"))):
        with open(os.path.join(tiny.BENCH, "configs", name)) as f:
            cfg = json.load(f)
        nets = circuits.Designs(cfg).circuits()
        assert {n.name: n.n_luts for n in nets} == cfg["luts"]
        assert {n.name: n.n_adders for n in nets} == cfg["adders"]
