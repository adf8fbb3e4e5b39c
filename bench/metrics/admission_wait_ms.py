"""core/runtime/scheduler.py and wlm.py: mean milliseconds from submission
to admission per executed query (the handle's ``queue_wait_ms``: the wait
for a query worker and the WLM admission span); result-cache hits are left
out."""


def read(run):
    waits = [r["queue_wait_ms"] for r in run.done
             if not r.get("cache_hit") and r.get("queue_wait_ms") is not None]
    return sum(waits) / len(waits) if waits else None
