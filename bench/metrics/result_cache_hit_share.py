"""core/serving: the share of the window's answered queries that the
serving tier's result cache answered (its ``hits`` counter), in %."""


def read(run):
    if not run.done:
        return None
    return 100.0 * run.counters.get("result_cache.hits", 0) / len(run.done)
