"""core/runtime/exec.py kernel sites: XLA backend compilations from the
window's start to its last answer (``jax.monitoring`` events), in the power
cell."""


def read(run):
    return run.counters.get("compiles", 0)
