"""The plain reference and the comparison that decides ``correct``.

The reference is stdlib ``sqlite3`` loaded with the same generated rows,
the same as ``chip_smoke.py``'s.  It imports nothing of the program.  The
comparison pairs each result row with its reference row and reads two
numbers: the widest relative gap of any float value, and the count of
values that differ where the comparison is exact (row counts, integers,
strings, NULLs).

The control is the reference with every ``SUM`` computed in float32, the
precision the device's kernels compute in: an aggregate that rounds every
term to float32 and accumulates in float32.
"""
from __future__ import annotations

import math
import re
import sqlite3

import numpy as np

_BATCH = 200_000


def sqlite_reference(tables: dict, keys: dict, control: bool = False,
                     sqls=None) -> sqlite3.Connection:
    """An in-memory sqlite3 database holding the same generated rows, each
    table's ``keys`` column declared its primary key; with ``control``,
    ``F32SUM`` is registered for :func:`control_sql`.  Given ``sqls``, only
    the tables and columns they name are loaded."""
    words = (set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", " ".join(sqls)))
             if sqls is not None else None)
    db = sqlite3.connect(":memory:")
    for name, cols in tables.items():
        if words is not None and name not in words:
            continue
        names = [c for c in cols if words is None or c in words]
        decl = ", ".join(
            f"{c} {_sqlite_type(cols[c])}"
            + (" PRIMARY KEY" if keys.get(name) == c else "")
            for c in names)
        db.execute(f"CREATE TABLE {name} ({decl})")
        insert = f"INSERT INTO {name} VALUES ({', '.join('?' * len(names))})"
        n = len(cols[names[0]])
        for lo in range(0, n, _BATCH):
            db.executemany(insert, zip(*(cols[c][lo:lo + _BATCH].tolist()
                                         for c in names)))
    db.commit()
    if control:
        db.create_aggregate("F32SUM", 1, _Float32Sum)
    return db


def _sqlite_type(arr) -> str:
    return {"i": "INTEGER", "u": "INTEGER", "f": "REAL"}.get(arr.dtype.kind,
                                                            "TEXT")


class _Float32Sum:
    """SUM with every term rounded to float32 and a float32 accumulator."""

    def __init__(self):
        self.acc = np.float32(0.0)
        self.n = 0

    def step(self, value):
        if value is not None:
            self.acc = np.float32(self.acc + np.float32(value))
            self.n += 1

    def finalize(self):
        return float(self.acc) if self.n else None


def control_sql(sql: str) -> str:
    """The query with every ``SUM`` computed in float32."""
    return re.sub(r"\bSUM\(", "F32SUM(", sql, flags=re.IGNORECASE)


def _plain(v):
    v = v.item() if hasattr(v, "item") else v
    if isinstance(v, float) and math.isnan(v):
        return None  # the engine's NULL in float columns
    return v


def _row_key(row):
    # group keys are unique per result row, so ordering by the non-float
    # values pairs each row with its reference row
    return tuple((0, "") if v is None else (1, str(v)) if isinstance(v, str)
                 else (2, v) for v in row if not isinstance(v, float))


def compare(got, want) -> tuple:
    """``(rel_gap, mismatched)`` of result rows ``got`` against reference
    rows ``want``, row order ignored.

    ``rel_gap`` is the widest ``|got - want| / max(|want|, 1)`` over the
    float values; ``mismatched`` counts values that differ where equality
    is exact, and every row of the longer side when the row counts differ.
    """
    got = sorted((tuple(_plain(v) for v in r) for r in got), key=_row_key)
    want = sorted((tuple(r) for r in want), key=_row_key)
    if len(got) != len(want):
        return 0.0, max(len(got), len(want))
    gap, mismatched = 0.0, 0
    for g, w in zip(got, want):
        if len(g) != len(w):
            mismatched += max(len(g), len(w))
            continue
        for a, b in zip(g, w):
            if isinstance(b, float) and isinstance(a, (int, float)):
                gap = max(gap, abs(a - b) / max(abs(b), 1.0))
            elif b is None or isinstance(b, str):
                mismatched += a != b
            else:
                mismatched += not (isinstance(a, (int, float)) and a == b)
    return gap, mismatched
