"""Programs compiled or loaded inside the window, counted from JAX's
monitoring events; every shape should be ready before it opens, so this
reads 0 (plan and registry layer; moves served_rps)."""


def read(run):
    return run.counters.get("window_compiles")
