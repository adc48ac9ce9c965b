"""LUT rows a call evaluates, padding included, per real LUT: the
evaluator's own plans and group stats over the circuits' LUT count."""


def read(run):
    padded = run.counters.get("eval_padded_lut_rows")
    real = run.counters.get("eval_real_lut_rows")
    if not padded or not real:
        return None
    return padded / real
