"""Envelope groups in flight when the eval program fetches a result: Σ
``queued`` / number of ``repro.eval.get`` spans that carry it, over the
window.  ``queued`` counts the call's groups dispatched and not yet
fetched when the get began, this one included: a call that dispatches
all of its ``n`` groups before fetching any reads (n + 1) / 2, one that
fetches each group before dispatching the next reads 1.  A program whose
gets carry no such stat reports nothing."""
from bench.program_spans import load, root_of


def read(run):
    sp = load(run, root_of(__file__))
    if sp is None:
        return None
    queued = [st["queued"]
              for _, _, st in sp.by_name.get("repro.eval.get", ())
              if "queued" in st]
    if not queued:
        return None
    return sum(queued) / len(queued)
