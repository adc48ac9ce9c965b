"""Cycle-simulation traffic: ``flow.evaluate_sequential`` on a systolic
array (``circuits.systolic_ws``), call after call, each call streaming
fresh weight and activation tiles through every one of its independent
array instances, one per lane.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``tiles`` — weight tiles per call: each a fresh ``R x C`` int8 matrix
  per instance, shifted in while the previous tile streams (the first
  one before any streams);
* ``tile_rows`` — rows of ``R`` int8 activations per tile, fed skewed;
* ``cycles`` — the call's cycles: load, stream and drain
  (:func:`bench.reference.systolic.n_cycles`, checked at set-up);
* ``lane_words`` — uint32 words per primary input and cycle: ``32 x
  lane_words`` independent instances of the array;
* ``use_pallas`` — evaluate LUT levels with the ``lut_eval6`` kernel;
* ``check_calls`` — how many of the window's calls the reference
  re-simulates, every output word of every cycle (drawn from the seed);
* ``trace_seconds`` — the traced window of a ``--trace 1`` run.

Call ``i`` draws its operands from ``(seed, i)`` as packed lanes: bit
``b`` of 32 instances' values in one word.  The warm-up call uses
operands no window call uses.  The reference is the array's register-
transfer semantics in numpy (:mod:`bench.reference.systolic`), run on
the operands unpacked per instance.  ``lut_evals_per_s`` is the sum over
the window's calls of cycles x real LUTs x lane words x 32, divided by
the time from the window's start to the last call's completion.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.circuits import DigestMismatch
from bench.harness import BenchError, Check
from bench.reference import seq as ref_seq
from bench.reference import systolic as ref_sys

ONES = np.uint32(0xFFFFFFFF)
#: the int8 operands' width, and the partial sums' width of the control:
#: the reference with int16 partial-sum registers, the integer type below
#: the configuration's int32.  A column sums 32 int8 x int8 products, so
#: its sums need 21 bits: 16-bit registers overflow them by value
OPERAND_BITS = 8
CONTROL_PSUM_BITS = 16


def _entropy(seed: int, call: int) -> list[int]:
    return [seed % 2**64, call % 2**64]


class Cell:
    def __init__(self, designs, traffic: dict, seed: int, log=print):
        self.designs = designs
        self.traffic = traffic
        self.seed = seed
        self.log = log
        self.n_words = int(traffic["lane_words"])
        self.n_tiles = int(traffic["tiles"])
        self.M = int(traffic["tile_rows"])
        self.use_pallas = bool(traffic.get("use_pallas", True))
        self.kept: list[tuple[int, np.ndarray]] = []

    # -- operands and streams ----------------------------------------------
    def _operands(self, call: int):
        """Packed lane words of call ``call``'s operands: ``W [n, R, C,
        8, N]`` and ``X [n, M, R, 8, N]``."""
        rng = np.random.default_rng(_entropy(self.seed, call))
        n, R, C, N = self.n_tiles, self.R, self.C, self.n_words
        W = rng.integers(0, 2**32, size=(n, R, C, OPERAND_BITS, N),
                         dtype=np.uint32)
        X = rng.integers(0, 2**32, size=(n, self.M, R, OPERAND_BITS, N),
                         dtype=np.uint32)
        return W, X

    def _stream(self, W, X) -> np.ndarray:
        """``[T, P, N]`` PI lanes of each cycle, ``net.pis`` order, on the
        schedule of :mod:`bench.reference.systolic`."""
        R, C, M = self.R, self.C, self.M
        out = np.zeros((self.T, self.n_pis, self.n_words), dtype=np.uint32)
        for k in range(self.n_tiles):
            for i in range(R):
                c = ref_sys.x_cycle(R, M, k, 0, i)
                out[c:c + M, self.x_rows[i]] = X[k, :, i]
                out[ref_sys.swap_cycle(R, M, k, i), self.swap_rows[i]] = ONES
            for j in range(C):
                c = ref_sys.shift_cycle(R, M, k, j, 0)
                out[c:c + R, self.w_rows[j]] = W[k, ::-1, j]
                out[c:c + R, self.shift_rows[j]] = ONES
        return out

    def _call(self, stream):
        from repro.core import flow

        outs, stats = flow.evaluate_sequential(
            [self.net], [stream], use_pallas=self.use_pallas)
        return outs[0], stats

    # -- the run -------------------------------------------------------------
    def setup(self, seconds: float) -> None:
        nets = self.designs.circuits()
        if len(nets) != 1:
            raise BenchError(f"stream traffic runs one array, the "
                             f"configuration makes {len(nets)} circuits")
        self.net = net = nets[0]
        pins = self.designs.config["reg_digests"]
        if ref_seq.reg_digest(net) != pins.get(net.name):
            raise DigestMismatch(f"{net.name}: registers differ from their "
                                 f"pinned digest")
        buses = net.pi_buses
        self.R, self.C = len(buses["wswap"]), len(buses["wshift"])
        self.T = ref_sys.n_cycles(self.R, self.C, self.n_tiles, self.M)
        if self.T != int(self.traffic["cycles"]):
            raise BenchError(f"the schedule runs {self.T} cycles, the "
                             f"traffic states {self.traffic['cycles']}")
        slot = {s: i for i, s in enumerate(net.pis)}
        self.n_pis = len(net.pis)

        def rows(name):
            return np.array([slot[s] for s in buses[name]], dtype=np.int64)

        self.x_rows = [rows(f"x{i}") for i in range(self.R)]
        self.w_rows = [rows(f"w{j}") for j in range(self.C)]
        self.shift_rows = rows("wshift")
        self.swap_rows = rows("wswap")
        self.psum_bits = len(net.pos["y0"])
        self.po_names = list(net.pos)
        if self.po_names != [f"y{j}" for j in range(self.C)]:
            raise BenchError(f"{net.name}: outputs {self.po_names[:3]}... "
                             f"are not the bottom row's partial sums")
        self.real_luts = net.n_luts
        # the warm-up call compiles the one program the window runs
        self._call(self._stream(*self._operands(2**63)))
        self.log(f"stream: warm-up of {self.T} cycles, {self.real_luts} "
                 f"LUTs x {self.n_words} words, {len(net.regs)} "
                 f"registers")

    def window(self, seconds: float, run) -> dict:
        rng = np.random.default_rng(_entropy(self.seed, 2**62))
        k = int(self.traffic["check_calls"])
        calls = 0
        stats = None
        t_start = time.perf_counter()
        deadline = t_start + seconds
        t_end = t_start
        while time.perf_counter() < deadline:
            with TraceAnnotation("bench.stream.operands"):
                stream = self._stream(*self._operands(calls))
            with TraceAnnotation("bench.stream.call"):
                pos, stats = self._call(stream)
            t_end = time.perf_counter()
            # reservoir sample of the calls the reference re-simulates
            slot = calls if calls < k else int(rng.integers(0, calls + 1))
            if slot < k:
                if calls < k:
                    self.kept.append((calls, pos))
                else:
                    self.kept[slot] = (calls, pos)
            calls += 1
        elapsed = max(t_end - t_start, 1e-9)
        run.counters.update(seq_calls=calls, seq_cycles=self.T,
                            seq_real_lut_rows=self.real_luts,
                            seq_lane_words=self.n_words)
        if stats is not None:
            run.counters["seq_padded_lut_rows"] = stats["padded_lut_rows"]
        return {"window_s": elapsed, "attempted": calls, "failed": 0,
                "metrics": {"lut_evals_per_s": calls * self.T
                            * self.real_luts * self.n_words * 32
                            / elapsed}}

    def release(self) -> None:
        self.net = None

    def _words(self, y: np.ndarray) -> np.ndarray:
        """``y [T, B, C]`` partial sums -> ``[T, C x bits, N]`` lane words
        in the primary outputs' order."""
        return np.concatenate([ref_sys.pack_bits(y[:, :, j], self.psum_bits)
                               for j in range(self.C)], axis=1)

    def check(self, control: bool = False) -> list[Check]:
        """Re-simulate every sampled call with the plain register-transfer
        model; every output word of every cycle must match.  With
        ``control`` the model with 16-bit partial sums, sign-extended to
        the program's 32-bit words, stands in for the program: it is
        wrong by value wherever a sum leaves the int16 range."""
        bad = words = 0
        for call, pos in self.kept:
            W, X = self._operands(call)
            W = ref_sys.unpack_int8(W).transpose(0, 3, 1, 2)
            X = ref_sys.unpack_int8(X).transpose(0, 3, 1, 2)
            st = ref_sys.streams(W, X)
            want = self._words(ref_sys.simulate(st, self.psum_bits))
            if control:
                got = self._words(ref_sys.sign_extend(
                    ref_sys.simulate(st, CONTROL_PSUM_BITS),
                    CONTROL_PSUM_BITS))
            else:
                got = pos
            if got.shape != want.shape:
                bad += want.size
            else:
                bad += int(np.count_nonzero(got != want))
            words += want.size
        self.log(f"stream: checked {len(self.kept)} calls, {words} output "
                 f"words")
        return [Check("po_word_mismatches", bad, 0),
                Check("calls_unchecked", 0 if self.kept else 1, 0)]
