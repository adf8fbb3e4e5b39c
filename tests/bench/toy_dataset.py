"""A dataset module for the harness's tests, with nothing of SSB in it.

Two tables, ``sale`` (the fact rows) and ``store``, and three statements
the warehouse runs: a join with a ``GROUP BY``, a ``GROUP BY`` over a
function of a date, and an ``EXISTS``.  It provides what the harness asks
of a configuration's ``"dataset"`` and nothing more.
"""
import re

import numpy as np

REGIONS = ("NORTH", "SOUTH", "EAST", "WEST")

PUBLISHED = {
    "join": "select st_region, sum(sa_cents) as cents from sale, store "
            "where sa_store = st_key and sa_qty < 30 "
            "group by st_region order by st_region",
    "year": "select sa_store, count(*) as n, sum(sa_qty) as qty from sale "
            "where year(sa_day) = 1996 group by sa_store order by sa_store",
    "exists": "select st_region, count(*) as n from store where exists "
              "(select * from sale where sa_store = st_key and sa_qty > 48) "
              "group by st_region order by st_region",
}
TEMPLATES = {
    "join": "select st_region, sum(sa_cents) as cents from sale, store "
            "where sa_store = st_key and sa_qty < {qty} "
            "group by st_region order by st_region",
    "year": "select sa_store, count(*) as n, sum(sa_qty) as qty from sale "
            "where year(sa_day) = {year} and sa_qty > {qty} "
            "group by sa_store order by sa_store",
    "exists": "select st_region, count(*) as n from store where exists "
              "(select * from sale where sa_store = st_key and sa_qty > "
              "{qty_hi}) group by st_region order by st_region",
}
KEYS = {"store": "st_key"}


def draw_params(rng) -> dict:
    return {"qty": int(rng.integers(5, 45)),
            "year": int(rng.integers(1995, 1998)),
            "qty_hi": int(rng.integers(40, 50))}


def generate(seed: int, config: dict, rehearse_rows=None) -> dict:
    rng = np.random.default_rng([seed, 0])
    n = config["sale_rows"] if rehearse_rows is None else rehearse_rows
    k = config["store_rows"]
    day = np.datetime64("1995-01-01") + rng.integers(0, 3 * 365, n)
    return {
        "store": {"st_key": np.arange(1, k + 1),
                  "st_region": np.array(REGIONS)[rng.integers(0, 4, k)]},
        "sale": {"sa_store": rng.integers(1, k + 1, n),
                 "sa_day": np.datetime_as_string(day).astype("U10"),
                 "sa_qty": rng.integers(1, 51, n),
                 "sa_cents": rng.integers(100, 100_000, n)},
    }


def warm_copy(tables: dict, stripe_rows: int) -> dict:
    out = dict(tables)
    out["sale"] = {c: v[:stripe_rows] for c, v in tables["sale"].items()}
    return out


def warm_bound(tables: dict) -> int:
    return len(tables["store"]["st_key"])


def reference_sql(sql: str) -> str:
    """sqlite3 has no ``year()``: the reference reads the date's digits."""
    return re.sub(r"\byear\((\w+)\)", r"cast(substr(\1, 1, 4) as integer)",
                  sql)


def load(wh, tables: dict) -> None:
    from repro.core.acid import AcidTable
    from repro.core.runtime.vector import VectorBatch

    types = {"i": "BIGINT", "U": "STRING"}
    s = wh.session()
    for name, cols in tables.items():
        s.execute(f"CREATE TABLE {name} (" + ", ".join(
            f"{c} {types[v.dtype.kind]}" for c, v in cols.items()) + ")")
    tx = wh.hms.open_txn()
    for name, cols in tables.items():
        AcidTable(wh.hms.get_table(name), wh.hms).insert(
            tx, VectorBatch(dict(cols)))
    wh.hms.commit_txn(tx)
