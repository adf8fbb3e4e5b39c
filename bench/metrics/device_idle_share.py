"""device: the share of the traced window in which no operation ran on the
chip, ``1 - busy / window``, in %."""


def read(run):
    p = run.profile
    if not p or "busy_s" not in p or p["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
