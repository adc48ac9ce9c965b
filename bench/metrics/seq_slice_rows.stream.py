"""Share of the value-buffer rows the cycle program writes by slice: Σ
``slice_rows`` / Σ (``slice_rows`` + ``scatter_rows``) over the window's
``repro.seq.run`` spans.  A call counts cycles x rows: the source block
and every level's outputs, padding included.  A program whose span
carries no such stats reports nothing."""
from bench.program_spans import load, root_of


def read(run):
    sp = load(run, root_of(__file__))
    if sp is None:
        return None
    sliced = sp.stat_sum("repro.seq.run", "slice_rows")
    scattered = sp.stat_sum("repro.seq.run", "scatter_rows")
    if sliced is None or scattered is None or not sliced + scattered:
        return None
    return 100.0 * sliced / (sliced + scattered)
