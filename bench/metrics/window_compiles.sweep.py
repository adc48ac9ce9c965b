"""Programs compiled inside the measured window, by JAX's own monitoring
events (persistent-cache loads are not compiles)."""


def read(run):
    return run.counters.get("window_compiles")
