#!/usr/bin/env python3
"""Read the control: the reference with every SUM in float32, put in the
program's place, at a cell's own size.

    python bench/control.py --workload ssb-sf1.power --seeds 11 12 13

For each seed it draws the cell's tables and every statement its window
can send, answers each with the control, and hands those answers to the
harness's own check and decision (``harness._check``, ``harness.decide``),
as a run hands the program's.  It prints one JSON line a seed with
``correct`` and the numbers compared beside their limits.  The benchmark's
own runs never run it; its readings set the upper end of each limit
(``PERF.md``).  It needs no accelerator: the control replaces the program.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, loadgen, reference  # noqa: E402


def control_records(dataset, tables, sqls) -> list:
    """The control's answer to each statement, as a run's records."""
    db = reference.sqlite_reference(tables, dataset.KEYS, control=True,
                                    sqls=sqls)
    records = [{"label": "control", "sql": sql,
                "rows": db.execute(reference.control_sql(
                    dataset.reference_sql(sql))).fetchall()}
               for sql in sqls]
    db.close()
    return records


def read(cell, seed: int, seconds: float) -> dict:
    config, data = cell.config, cell.dataset
    tables = data.generate(seed, config)
    traffic = loadgen.Traffic(cell.traffic, data, seed, seconds)
    sent = {sql for _label, sql in traffic.statements()}
    records = control_records(data, tables,
                              sorted(traffic.reference_sample(sent)))
    checks = harness._check(tables, traffic, records, config)
    return {"workload": cell.name, "seed": seed,
            "correct": harness.decide(checks),
            "checks": {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.ROOT, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        out = read(cell, seed, args.seconds)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
