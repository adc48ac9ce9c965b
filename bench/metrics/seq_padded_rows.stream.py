"""LUT rows one cycle of the step program evaluates, padding included,
per real LUT: the counts ``flow.evaluate_sequential`` reports of its
plan, over the circuit's LUT count."""


def read(run):
    padded = run.counters.get("seq_padded_lut_rows")
    real = run.counters.get("seq_real_lut_rows")
    if not padded or not real:
        return None
    return padded / real
