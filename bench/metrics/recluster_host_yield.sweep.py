"""Share of the re-cluster's hosting probes that leave a LUT hosted in an
adder ALM: Σ (``hosted`` − ``unhosted``) / Σ ``host_probes`` over the
window's ``repro.pack.cluster`` spans.  A probe is one scan of one LB's
hostable ALMs for a LUT or a pair of LUTs.  A hosting taken back
(``unhosted``: the first half of a split pair whose second half found
no ALM) is a probe spent for nothing, like one that hosts nothing."""
from bench.program_spans import load, root_of


def read(run):
    sp = load(run, root_of(__file__))
    if sp is None:
        return None
    probes = sp.stat_sum("repro.pack.cluster", "host_probes")
    hosted = sp.stat_sum("repro.pack.cluster", "hosted")
    unhosted = sp.stat_sum("repro.pack.cluster", "unhosted")
    if not probes or hosted is None or unhosted is None:
        return None
    return 100.0 * (hosted - unhosted) / probes
