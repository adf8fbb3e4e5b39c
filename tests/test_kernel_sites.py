"""The executor's kernel sites: what a ``kernel.key_lookup`` span says about
the call, the numpy fallbacks each site counts, and the grouped SUM's guard
on float32 accumulation."""
import numpy as np
import pytest

import repro.api as db
from repro.core.obs.trace import QueryTrace
from repro.core.runtime.exec import ExecContext
from repro.kernels.key_lookup.key_lookup import COMPARE_MAX_ENTRIES


def _key_lookup_spans(trace):
    return [args for name, _cat, _t0, _t1, _track, args in trace._spans
            if name == "kernel.key_lookup"]


@pytest.mark.parametrize("entries,path", [
    (5, "compare"), (COMPARE_MAX_ENTRIES, "compare"),
    (COMPARE_MAX_ENTRIES + 1, "blocked")])
def test_key_lookup_span_carries_entries_and_path(entries, path):
    ctx = ExecContext(None, None, config={"engine": "pallas"})
    ctx.trace = QueryTrace("q1")
    dictionary = np.arange(entries, dtype=np.float32)
    probe = np.array([-1.0, 0.0, entries - 1.0, entries], np.float32)
    codes = ctx.kernel_call("key_lookup", dictionary, probe)
    assert codes.tolist() == [-1, 0, entries - 1, -1]
    [args] = _key_lookup_spans(ctx.trace)
    assert args["entries"] == entries and args["path"] == path
    assert args["engine"] == "pallas" and args["h2d_bytes"] > 0


@pytest.fixture
def conn(tmp_path):
    c = db.connect(str(tmp_path / "wh"), engine="pallas",
                   **{"result_cache": False, "serving.result_cache": False})
    cur = c.cursor()
    cur.execute("CREATE TABLE f (k STRING, n BIGINT, v DOUBLE)")
    cur.execute("INSERT INTO f VALUES ('a', 1, 0.1), ('b', 2, 0.2), "
                "('c', 3, 0.25)")
    cur.execute("CREATE TABLE d (k STRING, n BIGINT, name STRING)")
    cur.execute("INSERT INTO d VALUES ('a', 1, 'x'), ('c', 3, 'y')")
    yield c
    c.close()


def _fallbacks(conn, sql):
    cur = conn.cursor().execute(sql)
    cur.fetchall()
    return cur.info.get("kernel_fallbacks", {})


def test_string_join_key_counts_a_key_lookup_fallback(conn):
    assert _fallbacks(conn, "select f.n from f, d where f.k = d.k") \
        .get("key_lookup", 0) >= 1
    assert "key_lookup" not in _fallbacks(
        conn, "select f.k from f, d where f.n = d.n")
    counters = conn.metrics()["counters"]
    assert counters["kernels.fallback.key_lookup"] >= 1


def test_filter_the_kernel_cannot_take_counts_a_fallback(conn):
    assert _fallbacks(conn, "select n from f where k like 'a%'") \
        .get("filter_eval", 0) >= 1
    assert "filter_eval" not in _fallbacks(conn,
                                           "select k from f where n < 3")


def test_sum_of_values_float32_cannot_hold_counts_a_fallback(conn):
    # 0.1 is not a float32 value: the sum runs in numpy
    assert _fallbacks(conn, "select k, sum(v) from f group by k") \
        .get("hash_group", 0) >= 1
    assert "hash_group" not in _fallbacks(
        conn, "select k, sum(n) from f group by k")


def test_auto_engine_asks_for_no_kernel(tmp_path):
    with db.connect(str(tmp_path / "wh")) as c:
        cur = c.cursor()
        cur.execute("CREATE TABLE f (k STRING, v DOUBLE)")
        cur.execute("INSERT INTO f VALUES ('a', 0.1)")
        cur = cur.execute("select k, sum(v) from f where k like 'a%' "
                          "group by k")
        cur.fetchall()
        assert "kernel_fallbacks" not in cur.info


@pytest.mark.parametrize("rows,kernel", [(100, True), (8000, False)])
def test_float_sum_takes_the_kernel_only_while_float32_holds_it(
        tmp_path, rows, kernel):
    """Values of 1024.25 are float32 values, but one group of 8000 of them
    sums past 2^22, where float32 has no room for the quarter: that sum
    runs in numpy, exactly, and two groups of 50 run on the kernel,
    exactly."""
    with db.connect(str(tmp_path / "wh"), engine="pallas",
                    **{"result_cache": False,
                       "exchange.batch_rows": 8192}) as c:
        cur = c.cursor()
        cur.execute("CREATE TABLE q (g BIGINT, v DOUBLE)")
        cur.execute("INSERT INTO q VALUES " + ", ".join(
            f"({i % 2 if rows < 1000 else 0}, 1024.25)" for i in range(rows)))
        cur = cur.execute("select g, sum(v) from q group by g")
        got = dict(cur.fetchall())
        assert sum(got.values()) == 1024.25 * rows  # exact
        assert len(got) == (2 if rows < 1000 else 1)
        assert ("hash_group" in cur.info.get("kernel_fallbacks", {})) \
            is not kernel
        assert (1024.25 * rows >= 2 ** 22) is not kernel
