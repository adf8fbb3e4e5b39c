"""core/runtime/exec.py kernel sites: XLA backend compilations from the
window's start to its last answer (``jax.monitoring`` events), in the dashboard
cell."""


def read(run):
    return run.counters.get("compiles", 0)
