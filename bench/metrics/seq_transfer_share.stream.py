"""Share of the stream window the program spends moving the cycle
simulation's streams between host and device: the ``repro.seq.put``
spans (the call's PI stream to the device, until it is there) and
``repro.seq.get`` spans (every cycle's outputs back) over the window."""
from bench.program_spans import root_of, window_share


def read(run):
    return window_share(run, root_of(__file__), "repro.seq.put",
                        "repro.seq.get")
