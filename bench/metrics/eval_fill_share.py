"""Share of the eval window the program spends filling host value
buffers: the ``repro.eval.fill`` spans (a zeroed buffer per program and
the primary inputs' lanes written into it) over the window."""
from bench.program_spans import root_of, window_share


def read(run):
    return window_share(run, root_of(__file__), "repro.eval.fill")
