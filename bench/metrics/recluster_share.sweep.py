"""Share of the sweep window spent re-clustering packs (the program's
``SweepResult.wall["recluster_s"]``, summed over the window's calls)."""


def read(run):
    if "recluster_s" not in run.spans or run.window_s <= 0:
        return None
    return 100.0 * run.spans["recluster_s"] / run.window_s
