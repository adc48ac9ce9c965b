"""The ``lut_eval6`` kernel's share of its roofline in the cycle
simulation: the least time the chip could take for the bytes the work
needs, over the kernel's summed device time in the trace.

Bytes per cycle are those of ``lut_eval6_roofline``: for each real LUT
and lane word, 6 input words read and 1 output word written (4 bytes
each), plus the LUT's two 32-bit table words; times the cycles of every
call of the window.  Padded rows are not counted.  There is no
operations term (``peaks.json`` has no 32-bit integer vector peak).
"""
from bench.trace import program_patterns


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds(program_patterns("lut_eval6")["ops"])
    calls = run.counters.get("seq_calls")
    cycles = run.counters.get("seq_cycles")
    luts = run.counters.get("seq_real_lut_rows")
    words = run.counters.get("seq_lane_words")
    bw = run.peaks.get("hbm_bytes_per_s")
    if not kernel_s or not calls or not cycles or not luts or not bw:
        return None
    bytes_ = calls * cycles * luts * (words * 7 * 4 + 2 * 4)
    return 100.0 * (bytes_ / bw) / kernel_s
