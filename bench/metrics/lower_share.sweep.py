"""Share of the sweep window spent lowering packs to the circuit IR: the
``repro.ir.lower`` spans (one per pack lowered, full or patched from the
circuit's template) over the window."""
from bench.program_spans import root_of, window_share


def read(run):
    return window_share(run, root_of(__file__), "repro.ir.lower")
