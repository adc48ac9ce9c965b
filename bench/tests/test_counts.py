"""The lut-vector and roofline-byte counts against hand counts for one
circuit, and the trace arithmetic the readers rest on."""
import os

import pytest

from bench import harness
from bench.trace import Trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _circuit():
    """Three LUTs and one 4-bit carry chain."""
    from repro.core.netlist import TT_AND2, TT_XOR2, Netlist

    net = Netlist("hand")
    a = net.add_pi_bus("a", 4)
    b = net.add_pi_bus("b", 4)
    x = net.add_lut((a[0], b[0]), TT_AND2)
    y = net.add_lut((a[1], b[1]), TT_XOR2)
    z = net.add_lut((x, y, a[2]), 0b10010110)
    s, _ = net.add_chain([x, y, z, a[3]], list(b))
    net.set_po_bus("s", s)
    net.set_po_bus("z", [z])
    return net


class _Designs:
    def __init__(self, nets):
        self.nets = nets

    def circuits(self):
        return self.nets


def test_lut_vectors_per_second_is_real_luts_times_vectors():
    from bench.kinds import eval as kind

    net = _circuit()
    assert net.n_luts == 3
    cell = kind.Cell(_Designs([net]), {"lane_words": 8, "use_pallas": False,
                                       "check_calls": 1},
                     seed=3, log=lambda m: None)
    cell.setup(0.5)
    run = harness.Run("hand", {})
    out = cell.window(0.5, run)
    calls = run.counters["eval_calls"]
    # 3 real LUTs x 8 words x 32 vectors per call, the chain not counted
    assert out["metrics"]["lut_evals_per_s"] == pytest.approx(
        calls * 3 * 8 * 32 / out["window_s"])
    assert run.counters["eval_real_lut_rows"] == 3
    assert run.counters["eval_padded_lut_rows"] >= 3
    cell.release()
    assert all(c.ok for c in cell.check())


def test_roofline_bytes_are_counted_by_hand():
    read = harness.load_metric(os.path.dirname(BENCH),
                               "lut_eval6_roofline").read
    # a kernel operation as the v5e trace prints it
    kernel = ('%closed_call.3 = u32[3,8]{1,0} custom-call(u32[3,2]{1,0} %t, '
              'u32[3,6,8]{2,1,0} %x), custom_call_target="tpu_custom_call"')
    # two calls over 3 LUTs at 8 words: per LUT and word 6 input words
    # read and 1 written (28 bytes), per LUT and call 2 table words (8)
    want_bytes = 2 * 3 * (8 * 28 + 8)
    assert want_bytes == 1392
    run = harness.Run("hand", {})
    run.counters.update(eval_calls=2, eval_real_lut_rows=3,
                        eval_lane_words=8)
    run.peaks = {"hbm_bytes_per_s": 819e9}
    # the kernel ran 1 us in all, in two events
    run.trace = Trace.from_events([
        ("/device:TPU:0", "XLA Ops", kernel, 1000, 400),
        ("/device:TPU:0", "XLA Ops", kernel, 3000, 600),
        ("/device:TPU:0", "XLA Ops", "fusion.7", 1400, 100)], 1.0)
    assert read(run) == pytest.approx(100 * (1392 / 819e9) / 1e-6)
    run.trace = Trace.from_events([], 1.0)
    assert read(run) is None        # no kernel in the trace: nothing read


def test_busy_time_is_the_union_of_op_intervals():
    t = Trace.from_events([
        ("/device:TPU:0", "XLA Ops", "a", 0, 100),
        ("/device:TPU:0", "XLA Ops", "b", 50, 100),     # overlaps a
        ("/device:TPU:0", "XLA Ops", "c", 400, 100),
        ("/device:TPU:1", "XLA Ops", "d", 0, 1000),     # chip not used
        ("/host:CPU", "python", "bench.eval.call", 0, 600),
    ], window_s=1e-6)
    assert t.busy_s() == pytest.approx(250e-9)
    assert t.idle_share() == pytest.approx(0.75)
    bd = t.breakdown()
    assert bd["idle_gaps"] == [["bench.eval.call", pytest.approx(250e-9)]]
    # named by program and opcode; "?" where no program run covers it
    assert bd["device_ops"][0][0] in ("?/a", "?/b", "?/c")
