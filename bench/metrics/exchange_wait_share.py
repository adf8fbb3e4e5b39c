"""core/runtime dag.py and exchange.py: the share of DAG vertex wall time
spent waiting on exchanges, summed over the window's traced queries, in %."""


def read(run):
    total = sum(r.get("vertex_ms", 0.0) for r in run.done)
    if total <= 0:
        return None
    return 100.0 * sum(r.get("vertex_wait_ms", 0.0) for r in run.done) / total
