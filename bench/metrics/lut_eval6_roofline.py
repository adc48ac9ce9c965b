"""The ``lut_eval6`` kernel's share of its roofline: the least time the
chip could take for the bytes the work needs, over the kernel's summed
device time in the trace.

Bytes per call: for each real LUT and lane word, 6 input words read and
1 output word written (4 bytes each), plus the LUT's two 32-bit table
words.  Padded rows are not counted, so the same work costs the same
whatever evaluates it.  There is no operations term: no peak of the
vector unit's 32-bit integer operations is published (``peaks.json``).
"""
from bench.trace import program_patterns


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds(program_patterns("lut_eval6")["ops"])
    luts = run.counters.get("eval_real_lut_rows")
    words = run.counters.get("eval_lane_words")
    calls = run.counters.get("eval_calls")
    bw = run.peaks.get("hbm_bytes_per_s")
    if not kernel_s or not luts or not calls or not bw:
        return None
    bytes_ = calls * luts * (words * 7 * 4 + 2 * 4)
    return 100.0 * (bytes_ / bw) / kernel_s
