"""Share of the eval window the program spends planning each call: the
``repro.eval.plan`` spans (plan lookups and their content digests,
grouping, the cost model, the suite program's preparation) over the
window."""
from bench.program_spans import root_of, window_share


def read(run):
    return window_share(run, root_of(__file__), "repro.eval.plan")
