"""Share of the sweep window spent building and running the timing
programs (``wall["build_s"] + wall["timing_s"]``; ``timing_s`` ends in
the program's host copy of the result, so it holds the device time)."""


def read(run):
    if "timing_s" not in run.spans or run.window_s <= 0:
        return None
    return 100.0 * (run.spans["build_s"] + run.spans["timing_s"]) \
        / run.window_s
