"""core/runtime/llap.py: the share of column-chunk reads in the window that
the LLAP cache served, ``cache_hits / (cache_hits + cache_misses)``, in %."""


def read(run):
    hits = run.counters.get("llap.cache_hits", 0)
    reads = hits + run.counters.get("llap.cache_misses", 0)
    return 100.0 * hits / reads if reads else None
