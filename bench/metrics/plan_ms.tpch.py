"""core/pipeline.py: mean milliseconds per query in the stages before
execution (parse, bind, cache probe, MV rewrite, optimize, compile), from
each answer's ``stage_times_ms``: ``plan_ms``'s reading, for TPC-H's plans
of up to eight tables with correlated subqueries."""


def read(run):
    per_query = [sum(ms for stage, ms in r["stage_ms"].items()
                     if stage != "execute")
                 for r in run.done if r.get("stage_ms")]
    return sum(per_query) / len(per_query) if per_query else None
