"""TPC-H through ``repro.api``: the 22 queries of ``bench/tpch.py`` as the
specification writes them (Q15 as its WITH variant), on the generator's
tables at about scale factor 0.005, against sqlite3 over the same rows; and
the SQL they need: DATE literals and interval arithmetic, EXTRACT,
SUBSTRING ... FROM ... FOR, WITH, derived-table column lists, correlated
subqueries whose correlation is not an equality, and join keys inside OR."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import repro.api as db  # noqa: E402
from bench import reference, tpch  # noqa: E402
from repro.core.session import Warehouse  # noqa: E402

LINEITEM_ROWS = 30_000  # every query answers at least one row at this size
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def tpch_db(tmp_path_factory):
    tables = tpch.generate(SEED, {"scale_factor": 1}, LINEITEM_ROWS)
    wh = Warehouse(str(tmp_path_factory.mktemp("tpch") / "wh"))
    tpch.load(wh, tables)
    conn = db.connect(warehouse=wh, engine="pallas", **{
        "shuffle.partitions": 1, "result_cache": False,
        "serving.result_cache": False})
    ref = reference.sqlite_reference(tables, tpch.KEYS)
    yield conn, ref
    ref.close()
    conn.close()
    wh.close()


@pytest.mark.parametrize("name", sorted(tpch.PUBLISHED,
                                        key=lambda n: int(n[1:])))
def test_query_equals_the_reference(tpch_db, name):
    conn, ref = tpch_db
    sql = tpch.PUBLISHED[name]
    got = conn.cursor().execute(sql).fetchall()
    want = ref.execute(tpch.reference_sql(sql)).fetchall()
    assert want, "the reference answers no row: the case checks nothing"
    gap, mismatched = reference.compare(got, want)
    assert mismatched == 0 and len(got) == len(want)
    assert gap <= 1e-9


def test_templates_fill_to_queries_that_run(tpch_db):
    """One fresh draw of every template parses, binds and runs."""
    conn, _ref = tpch_db
    params = tpch.draw_params(np.random.default_rng(3))
    for name, template in tpch.TEMPLATES.items():
        conn.cursor().execute(template.format(**params)).fetchall()


def test_generator_keeps_the_specification_shapes():
    t = tpch.generate(7, {"scale_factor": 1}, 20_000)
    orders, lines = t["orders"], t["lineitem"]
    keys = orders["o_orderkey"]
    assert np.all((keys - 1) % 32 < 8) and np.all(np.diff(keys) > 0)
    assert set(np.unique(lines["l_linenumber"])) <= set(range(1, 8))
    assert not np.any(orders["o_custkey"] % 3 == 0)
    assert max(orders["o_orderdate"].tolist()) <= "1998-08-02"
    ship, commit, receipt = (lines[c] for c in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert np.all(receipt > ship)
    assert set(lines["l_linestatus"][ship > "1995-06-17"]) == {"O"}
    assert set(lines["l_returnflag"][receipt > "1995-06-17"]) == {"N"}
    assert np.all(np.isin(t["partsupp"]["ps_suppkey"],
                          t["supplier"]["s_suppkey"]))
    assert any("Customer" in c and "Complaints" in c
               for c in t["supplier"]["s_comment"].tolist())
    assert np.array_equal(tpch.generate(7, {"scale_factor": 1}, 20_000)
                          ["lineitem"]["l_partkey"], lines["l_partkey"])


@pytest.fixture
def conn(tmp_path):
    c = db.connect(str(tmp_path / "wh"))
    cur = c.cursor()
    cur.execute("CREATE TABLE t (k BIGINT, d STRING, s STRING, v DOUBLE)")
    cur.execute("INSERT INTO t VALUES (1, '1995-01-31', 'ab-cd', 1.5), "
                "(2, '1996-02-29', 'xy-zw', 2.5), (3, '1998-12-01', 'ab-q', "
                "4.0)")
    cur.execute("CREATE TABLE u (k BIGINT, j BIGINT)")
    cur.execute("INSERT INTO u VALUES (1, 10), (1, 11), (2, 20), (3, 30)")
    yield c
    c.close()


def _rows(conn, sql):
    return sorted(conn.cursor().execute(sql).fetchall())


@pytest.mark.parametrize("expr,want", [
    ("date '1998-12-01' - interval '90' day (3)", "1998-09-02"),
    ("date '1995-01-31' + interval '1' month", "1995-02-28"),
    ("date '1996-01-31' + interval '1' month", "1996-02-29"),
    ("date '1996-02-29' + interval '1' year", "1997-02-28"),
    ("date '1994-03-31' - interval '1' month", "1994-02-28"),
    ("date '1995-12-15' + interval '3' month", "1996-03-15"),
])
def test_interval_arithmetic_folds_to_a_date(conn, expr, want):
    assert _rows(conn, f"select {expr} as x from t where k = 1") == [(want,)]


def test_date_literal_filters_iso_strings(conn):
    got = _rows(conn, "select k from t where d >= date '1996-01-01' and "
                      "d < date '1996-01-01' + interval '3' year")
    assert got == [(2,), (3,)]


def test_interval_needs_a_date_literal(conn):
    with pytest.raises(db.Error):
        conn.cursor().execute("select d + interval '1' day from t")


def test_extract_and_substring(conn):
    got = _rows(conn, "select extract(year from d), extract(month from d), "
                      "extract(day from d), substring(s from 1 for 2), "
                      "substring(s from 4) from t")
    assert got == [(1995, 1, 31, "ab", "cd"), (1996, 2, 29, "xy", "zw"),
                   (1998, 12, 1, "ab", "q")]


def test_substr_of_no_rows(conn):
    """Q22's shape once failed: substr and extract over an empty morsel."""
    got = _rows(conn, "select substr(s, 1, 2), extract(year from d) from t "
                      "where k > 9")
    assert got == []


def test_with_names_a_query_used_twice(conn):
    got = _rows(conn, "with big (kk, total) as (select k, sum(j) from u "
                      "group by k) select kk, total from big "
                      "where total = (select max(total) from big)")
    assert got == [(3, 30)]


def test_derived_table_column_list(conn):
    got = _rows(conn, "select n, count(*) from (select k, count(j) from u "
                      "group by k) as c (key, n) group by n")
    assert got == [(1, 2), (2, 1)]


def test_correlation_by_inequality_in_exists(conn):
    """EXISTS and NOT EXISTS whose subquery compares an outer column by
    ``<>``: Q21's shape."""
    conn.cursor().execute("INSERT INTO u VALUES (3, 30)")
    got = _rows(conn, "select a.k, a.j from u a where exists (select * from "
                      "u b where b.k = a.k and b.j <> a.j)")
    assert got == [(1, 10), (1, 11)]
    got = _rows(conn, "select a.k, a.j from u a where not exists (select * "
                      "from u b where b.k = a.k and b.j <> a.j)")
    assert got == [(2, 20), (3, 30), (3, 30)]


def test_subquery_table_shadowing_the_outer_one(conn):
    """The same table inside a correlated subquery and outside it: Q17's
    shape."""
    got = _rows(conn, "select u.k, j from u, t where u.k = t.k and j > "
                      "(select avg(j) from u where u.k = t.k)")
    assert got == [(1, 11)]


def test_join_key_inside_an_or_is_a_key(conn):
    plan = "\n".join(r[0] for r in conn.cursor().execute(
        "explain select t.k from t, u where (t.k = u.k and u.j = 10) or "
        "(t.k = u.k and u.j = 30)").fetchall())
    assert "cross" not in plan.lower()
    assert _rows(conn, "select t.k from t, u where (t.k = u.k and u.j = 10) "
                       "or (t.k = u.k and u.j = 30)") == [(1,), (3,)]


def test_having_compares_with_a_scalar_subquery(conn):
    got = _rows(conn, "select k, sum(j) from u group by k having sum(j) > "
                      "(select sum(j) * 0.3 from u)")
    assert got == [(3, 30)]
