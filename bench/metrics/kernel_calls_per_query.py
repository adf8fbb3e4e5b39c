"""core/runtime/exec.py kernel sites: Pallas kernel dispatches in the
window (the ``kernels.dispatch.<k>[pallas]`` counts) per answered query."""


def read(run):
    if not run.done:
        return None
    calls = sum(n for k, n in run.counters.items() if k.endswith("[pallas]"))
    return calls / len(run.done)
