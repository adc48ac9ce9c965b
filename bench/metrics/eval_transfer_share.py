"""Share of the eval window the program spends moving value buffers
between host and device: the ``repro.eval.put`` spans (host to device,
until the buffer is on the device) and ``repro.eval.get`` spans (the
result back to the host) over the window."""
from bench.program_spans import root_of, window_share


def read(run):
    return window_share(run, root_of(__file__), "repro.eval.put",
                        "repro.eval.get")
