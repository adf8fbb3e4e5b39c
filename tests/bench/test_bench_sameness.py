"""What the SSB cells read, pinned.

Since the harness asks a configuration's dataset module for its tables,
warm copy, statements, warm bound and reference keys, the same seed still
gives the same tables, warm copy, statements, warm kernel shapes,
reference answers and control checks as the harness did when it knew SSB
by name (``PARENT``, taken with that harness)."""
import hashlib
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness, loadgen, reference  # noqa: E402

ROWS = 20_000  # a full stripe and a part one: the warm copy is a cut
SEEDS = (2**31 + 17, 5)
FILTERS = [(1, ("lt",), (25.0,)), (2, ("ge", "le"), (1.0, 3.0))]

# taken with the harness that knew SSB by name
PARENT = {
    ("ssb-sf1.power", 2**31 + 17): {
        "tables": "597472635f2ba3fc",
        "warm_copy": "40d4ecfae94b61fe",
        "statements": "a34569c8bd5b7d36",
        "reference": "c412830f20131a52",
        "warm_shapes": "51276640145490b3",
        "warm_shapes_full": "b9be7ce59980ce7c",
        "control_checks": "a3d581922ed2cf50",
    },
    ("ssb-sf1.power", 5): {
        "tables": "ed77917ca5859133",
        "warm_copy": "93d5398c1fbe1030",
        "statements": "a34569c8bd5b7d36",
        "reference": "aa7b9077e60b3cda",
        "warm_shapes": "51276640145490b3",
        "warm_shapes_full": "b9be7ce59980ce7c",
        "control_checks": "25a53629d3b667d1",
    },
    ("ssb-sf0.1.dashboard", 2**31 + 17): {
        "tables": "c78eec4695cadf96",
        "warm_copy": "c3b61e5b3d2de112",
        "statements": "626e00972bc67fd2",
        "reference": "f085b772b9ddfe34",
        "warm_shapes": "51276640145490b3",
        "warm_shapes_full": "c9b2a56df4a6373d",
        "control_checks": "49051a511980c623",
    },
    ("ssb-sf0.1.dashboard", 5): {
        "tables": "b879825368a6a2ec",
        "warm_copy": "a654fee3484c1d41",
        "statements": "cb8a021343a627cf",
        "reference": "e240efb1f3f07d6e",
        "warm_shapes": "51276640145490b3",
        "warm_shapes_full": "c9b2a56df4a6373d",
        "control_checks": "da4fec26ddce5ab4",
    },
}


def _feed(h, obj):
    if isinstance(obj, dict):
        h.update(b"{")
        for k, v in obj.items():
            h.update(repr(k).encode())
            _feed(h, v)
        h.update(b"}")
    elif isinstance(obj, np.ndarray):
        h.update(f"{obj.dtype.str}{obj.shape}".encode())
        h.update(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, (list, tuple)):
        h.update(b"[")
        for x in obj:
            _feed(h, x)
        h.update(b"]")
    else:
        h.update(repr(obj).encode())


def _digest(obj) -> str:
    h = hashlib.sha256()
    _feed(h, obj)
    return h.hexdigest()[:16]


def _shape_of(a):
    if isinstance(a, np.ndarray):
        return ("array", a.shape, a.dtype.str)
    if isinstance(a, (list, tuple)):
        return tuple(_shape_of(x) for x in a)
    return a


def _warm_calls(bound: int) -> list:
    """The kernel calls the warm-up makes for ``FILTERS`` under ``bound``,
    each as its kernel and its arguments' shapes."""
    sys.path.insert(0, str(ROOT / "src"))
    calls = []
    fake = SimpleNamespace(resolve=lambda name, _engine: (
        lambda *args: calls.append((name, _shape_of(args)))))
    harness._warm_shapes(fake, FILTERS, bound)
    return calls


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell_name", ["ssb-sf1.power", "ssb-sf0.1.dashboard"])
def test_ssb_cells_read_what_they_read_before(cell_name, seed):
    cell = harness.resolve_cell(ROOT, cell_name)
    data, config = cell.dataset, cell.config
    tables = data.generate(seed, config, ROWS)
    traffic = loadgen.Traffic(cell.traffic, data, seed, 51)
    statements = traffic.statements()
    streams = [[next(s) for _ in range(8)] for s in
               map(traffic.stream, range(int(cell.traffic["clients"])))]
    sample = sorted(traffic.reference_sample({sql for _l, sql in statements}))
    db = reference.sqlite_reference(tables, data.KEYS, sqls=sample)
    schema = [r[0] for r in db.execute(
        "select sql from sqlite_master order by name")]
    answers = [db.execute(data.reference_sql(sql)).fetchall()
               for sql in sample]
    db.close()
    checks = harness._check(tables, traffic,
                            control.control_records(data, tables, sample),
                            config)
    # the configuration's own sizes, without drawing six million rows
    full = {t: {"key": np.zeros(n)}
            for t, n in config["dimension_rows"].items()}
    warm = data.warm_copy(tables, harness.STRIPE_ROWS)
    assert all(warm[t] is tables[t] for t in tables if t != "lineorder")
    got = {
        "tables": _digest(tables),
        "warm_copy": _digest(warm),
        "statements": _digest([statements, streams]),
        "reference": _digest([sample, schema, answers]),
        "warm_shapes": _digest(_warm_calls(data.warm_bound(tables))),
        "warm_shapes_full": _digest(_warm_calls(data.warm_bound(full))),
        "control_checks": _digest(checks),
    }
    assert got == PARENT[(cell_name, seed)]
    assert not harness.decide(checks)
