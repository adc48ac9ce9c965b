"""The cycle-simulation traffic (``bench/kinds/stream.py``) on a small
systolic array, end to end through the harness on the CPU: its schedule
computes the tiles' matrix products, its comparison decides ``correct``,
and that comparison comes out false when the timed path is broken (an
altered output word, a dropped cycle) or when the control (16-bit
partial sums) stands in for the program.  Its configuration pins the
registers beside the netlist."""
import copy
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import harness
from bench.reference import seq as ref_seq
from bench.reference import systolic as ref_sys
from bench.reference.netlist import digest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = COLS = 2
KWARGS = {"name": "sa2", "rows": ROWS, "cols": COLS, "width": 8,
          "psum_width": 32, "algo": "wallace"}
TRAFFIC = {"kind": "stream", "tiles": 2, "tile_rows": 3,
           "cycles": ref_sys.n_cycles(ROWS, COLS, 2, 3), "lane_words": 1,
           "use_pallas": False, "check_calls": 2}
CELL = "sa2.stream"


def _config(kwargs: dict = KWARGS) -> dict:
    from repro.core.circuits import systolic_ws

    net = systolic_ws(**kwargs)
    name = kwargs["name"]
    return {"name": name,
            "suites": [{"suite": name, "generator": "systolic_ws",
                        "kwargs": kwargs,
                        "circuits": {name: digest(net)}}],
            "reg_digests": {name: ref_seq.reg_digest(net)}}


def _make_root(path: str, cfg: dict) -> str:
    os.makedirs(os.path.join(path, "bench", "configs"))
    os.makedirs(os.path.join(path, "bench", "traffic"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(path, "bench", "metrics"))
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = {"hbm_bytes_per_s": 1e10}
    with open(os.path.join(path, "bench", "peaks.json"), "w") as f:
        json.dump(peaks, f)
    with open(os.path.join(path, "bench", "configs", "sa2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(path, "bench", "traffic", "sa2.stream.json"),
              "w") as f:
        json.dump(TRAFFIC, f)
    metrics = ("lut_eval6_roofline.stream", "idle_share.stream",
               "seq_transfer_share.stream", "seq_padded_rows.stream")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "sa2", "source": "test",
                     "file": "bench/configs/sa2.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": CELL, "config": "sa2",
                       "traffic": "sa2.stream", "chips": 1,
                       "why": "test"}],
        "end_to_end": [
            {"name": "lut_evals_per_s", "unit": "lut-vectors/s",
             "better": "higher", "bound": 0.13, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": m, "unit": "%", "better": "lower",
                       "source": "program_span", "layer": "test",
                       "moves": "lut_evals_per_s"} for m in metrics],
    }
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return path


def _run(root: str, seed: int = 2**31 + 17, trace: bool = False) -> dict:
    import jax

    spec = harness.load_spec(root)
    return harness.run_cell(root, spec, CELL, seed, 1.0, trace,
                            time.perf_counter(), jax.devices(),
                            log=lambda msg: None)


@pytest.fixture(scope="module")
def cfg():
    return _config()


@pytest.fixture(scope="module")
def root(tmp_path_factory, cfg):
    return _make_root(str(tmp_path_factory.mktemp("bench_stream")), cfg)


@pytest.fixture(autouse=True)
def fresh_program_caches():
    """Each run is a process of its own: no test may find the results a
    broken program cached in an earlier one."""
    from repro.core import plan

    plan.clear_caches()
    yield
    plan.clear_caches()


@pytest.mark.parametrize("R,C,n,M", [(2, 2, 3, 3), (3, 4, 2, 6),
                                     (4, 4, 2, 7)])
def test_schedule_computes_the_tile_products(R, C, n, M):
    """On the schedule, column ``j`` shows ``Y_k[r, j]`` of every tile in
    its cycle, and the last output falls in the last cycle."""
    rng = np.random.default_rng(R * 100 + C)
    W = rng.integers(-128, 128, (n, 32, R, C), dtype=np.int8)
    X = rng.integers(-128, 128, (n, 32, M, R), dtype=np.int8)
    y = ref_sys.simulate(ref_sys.streams(W, X))
    Y = ref_sys.products(W, X)
    assert y.shape[0] == ref_sys.n_cycles(R, C, n, M)
    assert ref_sys.out_cycle(R, M, n - 1, M - 1, C - 1) == y.shape[0] - 1
    for k in range(n):
        for r in range(M):
            for j in range(C):
                assert np.array_equal(y[ref_sys.out_cycle(R, M, k, r, j),
                                        :, j], Y[k, :, r, j])


def test_lane_words_round_trip():
    v = np.random.default_rng(1).integers(-128, 128, (3, 64),
                                          dtype=np.int8)
    assert np.array_equal(ref_sys.unpack_int8(ref_sys.pack_bits(v, 8)), v)


def test_gate_level_stepping_equals_the_rtl_model(cfg):
    """The frozen evaluator stepping the generated netlist gives the
    register-transfer model's outputs on the traffic's own stream."""
    from bench.circuits import Designs
    from bench.kinds import stream as kind

    cell = kind.Cell(Designs(cfg), TRAFFIC, seed=5, log=lambda m: None)
    cell.setup(0.1)
    W, X = cell._operands(3)
    pis = cell._stream(W, X)
    net = cell.net
    T = TRAFFIC["cycles"]
    got = ref_seq.step(net, {s: [int(pis[t, i, 0]) for t in range(T)]
                             for i, s in enumerate(net.pis)}, T, 32)
    st = ref_sys.streams(ref_sys.unpack_int8(W).transpose(0, 3, 1, 2),
                         ref_sys.unpack_int8(X).transpose(0, 3, 1, 2))
    want = cell._words(ref_sys.simulate(st))
    po = [s for bus in net.pos.values() for s in bus]
    assert np.array_equal(
        np.array([[got[t][s] for s in po] for t in range(T)]), want[:, :, 0])


def test_stream_cell_runs_correct(root):
    res = _run(root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"lut_evals_per_s", "setup_s"}
    assert res["metrics"]["lut_evals_per_s"]["value"] > 0
    assert res["checks"]["po_word_mismatches"]["value"] == 0


def test_traced_run_reads_the_program_metrics(root):
    """On the CPU no operation lies on a TPU plane, so the device
    readings are absent; the program's span and counter are read."""
    res = _run(root, trace=True)
    assert res["correct"]
    assert res["metrics"]["seq_padded_rows.stream"]["value"] >= 1.0
    assert 0 < res["metrics"]["seq_transfer_share.stream"]["value"] < 100
    assert "idle_share.stream" not in res["metrics"]


def _seq_fault(kind):
    from repro.core import flow

    real = flow.evaluate_sequential

    def broken(nets, streams, **kw):
        outs, stats = real(nets, streams, **kw)
        out = np.array(outs[0])
        mid = out.shape[0] // 2
        if kind == "altered":        # one output word of one cycle flipped
            out[mid, 0, 0] ^= 1
        else:                        # one cycle dropped, the rest moved up
            out = np.concatenate([np.delete(out, mid, axis=0),
                                  np.zeros_like(out[:1])])
        return [out], stats

    return broken


@pytest.mark.parametrize("kind", ["altered", "dropped"])
def test_stream_fault_is_not_correct(root, monkeypatch, kind):
    from repro.core import flow

    monkeypatch.setattr(flow, "evaluate_sequential", _seq_fault(kind))
    res = _run(root)
    assert not res["correct"]
    assert res["checks"]["po_word_mismatches"]["value"] > 0


def test_control_is_not_correct():
    """The model with 16-bit partial sums, in the program's place, fails
    the comparison, as ``bench/control.py`` reads it on the chip.  A 4x4
    array sums 4 products a column, so some sums leave the int16 range
    and the control is wrong by value, not in sign bits alone."""
    from bench.circuits import Designs
    from bench.kinds import stream as kind

    cfg = _config(dict(KWARGS, name="sa4", rows=4, cols=4))
    traffic = dict(TRAFFIC, tile_rows=7, cycles=ref_sys.n_cycles(4, 4, 2, 7))
    c = kind.Cell(Designs(cfg), traffic, seed=2**31 + 3, log=lambda m: None)
    c.setup(1.0)
    c.window(1.0, harness.Run(CELL, traffic))
    c.release()
    program = {x.name: x for x in c.check()}
    control = {x.name: x for x in c.check(control=True)}
    assert program["po_word_mismatches"].ok
    assert not control["po_word_mismatches"].ok


@pytest.mark.parametrize("field", ["reg_digests", "circuits"])
def test_changed_pin_is_refused(cfg, tmp_path, field):
    from bench.circuits import DigestMismatch

    bad = copy.deepcopy(cfg)
    if field == "reg_digests":
        bad["reg_digests"]["sa2"] = "0" * 32
    else:
        bad["suites"][0]["circuits"]["sa2"] = "0" * 32
    root = _make_root(str(tmp_path), bad)
    with pytest.raises(DigestMismatch):
        _run(root)


def test_rewired_register_is_refused(cfg, tmp_path, monkeypatch):
    """A register fed from another signal leaves the netlist's own digest
    alone and changes the registers' pin."""
    from bench.circuits import DigestMismatch
    from repro.core import circuits as gen

    real = gen.systolic_ws

    def rewired(**kw):
        net = real(**kw)
        (d0, q0), (d1, q1) = net.regs[0], net.regs[1]
        net.regs[0], net.regs[1] = (d1, q0), (d0, q1)
        return net

    monkeypatch.setattr(gen, "systolic_ws", rewired)
    root = _make_root(str(tmp_path), cfg)
    with pytest.raises(DigestMismatch):
        _run(root)
