"""Observability layer (PR 10): per-query tracing, the warehouse metrics
registry, the always-on query log, and the trace-backed EXPLAIN ANALYZE.

Covers the acceptance contract of the obs subsystem:

  * tracing off is *free*: hot-path helpers return the shared NOOP_SPAN
    singleton (identity-checked — zero span allocations) and queries carry
    no QueryTrace;
  * tracing on records one span per pipeline stage and one vertex record
    per DAG vertex, with monotone timestamps and proper nesting;
  * the Chrome export validates (ph/ts/pid/tid present, B/E balanced,
    per-tid monotone) through ``repro.analysis.trace_check``;
  * ``poll()`` / ``server_stats()`` keep their historical dict shapes but
    now derive from the MetricsRegistry;
  * the query log is a bounded ring (oldest evicts first);
  * cache-served results report the same ``stage_times_ms`` keys as
    executed ones (satellite a).
"""
import glob
import json
import os

import numpy as np
import pytest

import repro.api as db
from repro.analysis.trace_check import validate_chrome_trace
from repro.core.obs import (NOOP_SPAN, MetricsRegistry, QueryLog, QueryTrace,
                            emit_event, make_span, tracing_enabled)

TRACED = {"obs.tracing": True}


@pytest.fixture()
def wh_dir(tmp_path):
    return str(tmp_path / "wh")


def _load_events(conn):
    conn.execute("CREATE TABLE ev (k BIGINT, grp BIGINT, val DOUBLE)")
    conn.execute(
        "INSERT INTO ev VALUES " + ", ".join(
            f"({i}, {i % 7}, {float(i) / 3:.4f})" for i in range(300)))


# ===========================================================================
# tracing off: no allocations, no traces
# ===========================================================================
class TestTracingOff:
    def test_make_span_returns_noop_singleton(self):
        s1 = make_span(None, "stage:parse", "stage")
        s2 = make_span(None, "vertex:v1", "vertex")
        assert s1 is NOOP_SPAN and s2 is NOOP_SPAN

    def test_emit_event_is_noop_without_trace(self):
        emit_event(None, "adaptive:skew", "adaptive", vid="v1")  # no raise

    def test_tracing_enabled_resolution(self, monkeypatch):
        monkeypatch.delenv("REPRO_OBS_TRACING", raising=False)
        assert tracing_enabled({"obs.tracing": False}) is False
        assert tracing_enabled({"obs.tracing": True}) is True
        monkeypatch.setenv("REPRO_OBS_TRACING", "1")
        assert tracing_enabled({"obs.tracing": False}) is True
        monkeypatch.setenv("REPRO_OBS_TRACING", "0")
        assert tracing_enabled({"obs.tracing": False}) is False

    def test_untraced_query_allocates_no_trace(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            h = conn.execute_async("SELECT grp, SUM(val) FROM ev GROUP BY grp")
            h.result()
            assert h._task.trace is None
            with pytest.raises(RuntimeError, match="tracing off"):
                h.trace()

    def test_query_log_records_even_untraced(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            conn.execute("SELECT COUNT(*) FROM ev").fetchall()
            log = conn.query_log()
            assert log, "query log must be always-on"
            assert {"qid", "sql", "status", "wall_ms"} <= set(log[-1])
            assert log[-1]["status"] == "SUCCEEDED"


# ===========================================================================
# tracing on: spans, vertices, Chrome export
# ===========================================================================
class TestTracedQuery:
    def test_stage_spans_and_vertex_records(self, wh_dir):
        # engine="ref": aggregate kernels only route when engine != auto
        with db.connect(wh_dir, engine="ref", **TRACED) as conn:
            _load_events(conn)
            h = conn.execute_async(
                "SELECT grp, SUM(k), COUNT(*) FROM ev "
                "WHERE k > 10 GROUP BY grp")
            h.result()
            trace = h._task.trace
            assert trace is not None
            summ = trace.summary()
            # every pipeline stage that ran shows up as a stage span
            for stage in ("parse", "bind", "optimize", "compile", "execute"):
                assert stage in summ["stages_ms"], summ["stages_ms"]
            # one vertex record per DAG vertex, wall split into sub-phases
            done = h.poll()
            assert len(summ["vertices"]) == done["vertices_total"]
            for vid, v in summ["vertices"].items():
                total = v["total_ms"]
                parts = (v["compute_ms"] + v["exchange_wait_ms"]
                         + v["spill_io_ms"])
                assert total >= 0 and parts <= total + 0.01, (vid, v)
            assert summ["kernel_dispatches"], "kernels must be counted"

    def test_vertex_phases_nest_where_rounding_meets(self):
        """A vertex's compute phase ends where its exchange wait begins, to
        the rounded microsecond, at a clock reading where adding the
        rounded duration to the rounded start overshoots by one bit."""
        trace = QueryTrace("q")
        trace.t0 = 6281.125483753391
        trace.add_vertex("v2", 6281.307919920278, 0.40652790111616094,
                         wait_s=0.0938333652930376)
        assert validate_chrome_trace(trace.to_chrome()) == []

    def test_chrome_export_validates(self, wh_dir):
        with db.connect(wh_dir, **TRACED) as conn:
            _load_events(conn)
            h = conn.execute_async(
                "SELECT grp, AVG(val) FROM ev GROUP BY grp")
            h.result()
            data = h.trace()
            assert validate_chrome_trace(data) == []
            events = data["traceEvents"]
            # balanced B/E with monotone, non-negative timestamps per tid
            opens = {}
            for ev in events:
                assert {"ph", "ts", "pid", "tid", "name"} <= set(ev)
                if ev["ph"] == "B":
                    opens.setdefault(ev["tid"], []).append(ev)
                elif ev["ph"] == "E":
                    assert opens[ev["tid"]], "E without open B"
                    b = opens[ev["tid"]].pop()
                    assert ev["ts"] >= b["ts"] >= 0
            assert all(not stack for stack in opens.values())

    def test_export_trace_roundtrip(self, wh_dir, tmp_path):
        with db.connect(wh_dir, **TRACED) as conn:
            _load_events(conn)
            h = conn.execute_async("SELECT COUNT(*) FROM ev")
            h.result()
            path = str(tmp_path / "trace.json")
            assert conn.export_trace(h.query_id, path) == path
            with open(path) as f:
                assert validate_chrome_trace(json.load(f)) == []
            with pytest.raises(KeyError):
                conn.export_trace("q999999", str(tmp_path / "x.json"))

    def test_stage_spans_nest_and_order(self):
        tr = QueryTrace("q1", "SELECT 1")
        with tr.span("stage:execute", "stage"):
            with tr.span("wlm:admission_wait", "wlm"):
                pass
        data = tr.to_chrome()
        rows = [(e["ph"], e["name"], e["ts"]) for e in data["traceEvents"]
                if e["ph"] in "BE"]
        names = [r[1] for r in rows]
        # inner span closes before the outer one
        assert names.index("wlm:admission_wait") \
            < names.index("stage:execute", 1) \
            or names == ["stage:execute", "wlm:admission_wait",
                         "wlm:admission_wait", "stage:execute"]
        ts = [r[2] for r in rows]
        assert ts == sorted(ts)


# ===========================================================================
# metrics registry as the single stats source
# ===========================================================================
class TestMetrics:
    def test_registry_primitives(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.inc("c", 2)
        reg.gauge("g", lambda: {"pool": 3})
        reg.observe("h_ms", 12.5)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 3
        assert snap["gauges"]["g"] == {"pool": 3}
        assert snap["histograms"]["h_ms"]["count"] == 1

    def test_serving_stats_shape_preserved_and_registry_backed(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            sql = "SELECT grp, COUNT(*) FROM ev GROUP BY grp"
            conn.execute(sql).fetchall()
            conn.execute(sql).fetchall()
            stats = conn.server_stats()
            # historical shape
            assert {"result_cache", "shared_scans",
                    "admission_queues"} <= set(stats)
            rc = stats["result_cache"]
            assert {"hits", "misses", "evictions", "fills"} <= set(rc)
            assert rc["hits"] >= 1
            # same numbers flow from the registry snapshot
            counters = conn.metrics()["counters"]
            assert counters["serving.result_cache.hits"] == rc["hits"]
            assert counters["serving.result_cache.misses"] == rc["misses"]
            sc = stats["shared_scans"]
            assert counters["serving.shared_scans.published"] \
                == sc["published"]

    def test_wlm_counters_in_registry(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            for ddl in ("CREATE RESOURCE PLAN obsplan",
                        "CREATE POOL obsplan.bi WITH alloc_fraction=1.0, "
                        "query_parallelism=4",
                        "ALTER PLAN obsplan SET DEFAULT POOL = bi",
                        "ALTER RESOURCE PLAN obsplan ENABLE ACTIVATE"):
                conn.execute(ddl)
            conn.execute_async("SELECT COUNT(*) FROM ev").result()
            m = conn.metrics()
            assert m["counters"].get("wlm.admitted", 0) >= 1
            assert "wlm.queue_depths" in m["gauges"]

    def test_kernel_dispatch_counts_surface(self, wh_dir):
        with db.connect(wh_dir, engine="ref") as conn:
            _load_events(conn)
            conn.execute("SELECT grp, SUM(k) FROM ev GROUP BY grp")
            m = conn.metrics()
            assert any(k.startswith("kernels.dispatch.")
                       for k in m["counters"])

    def test_query_outcome_counters(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            conn.execute("SELECT COUNT(*) FROM ev").fetchall()
            with pytest.raises(db.Error):
                conn.execute("SELECT nope FROM ev").fetchall()
            c = conn.metrics()["counters"]
            assert c.get("query.succeeded", 0) >= 1
            assert c.get("query.failed", 0) >= 1


# ===========================================================================
# query log ring
# ===========================================================================
class TestQueryLog:
    def test_ring_bounds_and_eviction(self):
        log = QueryLog(capacity=4)
        for i in range(10):
            log.record({"qid": f"q{i}"})
        assert len(log) == 4
        assert [e["qid"] for e in log.entries()] == ["q6", "q7", "q8", "q9"]
        assert [e["qid"] for e in log.entries(limit=2)] == ["q8", "q9"]

    def test_entries_are_copies(self):
        log = QueryLog(capacity=2)
        log.record({"qid": "q0"})
        log.entries()[0]["qid"] = "mutated"
        assert log.entries()[0]["qid"] == "q0"

    def test_failed_and_cancelled_logged(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            with pytest.raises(db.Error):
                conn.execute("SELECT nope FROM ev").fetchall()
            statuses = {e["status"] for e in conn.query_log()}
            assert "FAILED" in statuses
            failed = [e for e in conn.query_log()
                      if e["status"] == "FAILED"][-1]
            assert failed["error"]


# ===========================================================================
# satellite (a): cache-hit stage_times_ms parity
# ===========================================================================
class TestCacheHitStageParity:
    def test_same_keys_zeroed_post_probe(self, wh_dir):
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            sql = "SELECT grp, MAX(val) FROM ev GROUP BY grp"
            miss = conn.execute(sql).info
            hit = conn.execute(sql).info
            assert hit["cache_hit"] is True
            assert hit.get("admission_skipped") is True
            assert set(hit["stage_times_ms"]) == set(miss["stage_times_ms"])
            assert hit["stage_times_ms"]["execute"] == 0.0
            assert hit["stage_times_ms"]["compile"] == 0.0
            assert hit["stage_times_ms"]["parse"] > 0.0


# ===========================================================================
# trace-backed EXPLAIN ANALYZE
# ===========================================================================
class TestExplainAnalyze:
    def test_vertex_breakdown_and_events(self, wh_dir):
        with db.connect(wh_dir, engine="ref") as conn:
            _load_events(conn)
            cur = conn.execute(
                "EXPLAIN ANALYZE SELECT grp, SUM(k) FROM ev "
                "WHERE k > 5 GROUP BY grp")
            text = "\n".join(r[0] for r in cur.fetchall())
            assert "stage timings:" in text
            assert "vertex breakdown:" in text
            assert "compute=" in text and "exchange_wait=" in text \
                and "spill_io=" in text
            assert "kernel dispatches:" in text

    def test_analyze_forces_tracing_without_session_flag(self, wh_dir):
        # session tracing off: ANALYZE still gets a trace-backed report
        with db.connect(wh_dir) as conn:
            _load_events(conn)
            cur = conn.execute("EXPLAIN ANALYZE SELECT COUNT(*) FROM ev")
            text = "\n".join(r[0] for r in cur.fetchall())
            assert "vertex breakdown:" in text


# ===========================================================================
# kernel round trips, host-to-device bytes, scan reads, profiler mirror
# ===========================================================================
def _load_star(conn):
    conn.execute("CREATE TABLE fact (k BIGINT, d BIGINT, v BIGINT)")
    conn.execute("CREATE TABLE dim (d BIGINT, name STRING)")
    conn.execute("INSERT INTO fact VALUES " + ", ".join(
        f"({i}, {i % 50}, {i % 13})" for i in range(3000)))
    conn.execute("INSERT INTO dim VALUES " + ", ".join(
        f"({i}, 'n{i % 3}')" for i in range(50)))


STAR_SQL = ("SELECT dim.name, SUM(fact.v) FROM fact JOIN dim "
            "ON fact.d = dim.d WHERE fact.k > 10 GROUP BY dim.name")
STAR_ANSWER = sorted(
    (f"n{g}", sum(i % 13 for i in range(11, 3000) if i % 50 % 3 == g))
    for g in range(3))


def _forbid_spans(monkeypatch):
    from repro.core.obs import trace as trace_mod

    def refuse(*_a, **_k):
        raise AssertionError("a span was allocated with tracing off")

    monkeypatch.setattr(trace_mod._Span, "__init__", refuse)


class TestKernelSpans:
    def test_untraced_kernel_call_allocates_no_span(self, monkeypatch):
        from repro.core.obs.trace import make_kernel_span
        from repro.core.runtime.exec import ExecContext

        assert make_kernel_span(None, "key_lookup", "pallas") is NOOP_SPAN
        _forbid_spans(monkeypatch)
        ctx = ExecContext(None, None, config={"engine": "pallas"})
        assert ctx.trace is None
        codes = ctx.kernel_call(
            "key_lookup", np.array([1.0, 3.0, 5.0], np.float32),
            np.array([3.0, 4.0, 5.0], np.float32))
        assert isinstance(codes, np.ndarray)
        assert codes.tolist() == [1, -1, 2]

    def test_untraced_pallas_query_allocates_no_span(self, wh_dir,
                                                     monkeypatch):
        # every kernel, LLAP and scan site takes the no-op when untraced
        with db.connect(wh_dir, engine="pallas") as conn:
            _load_star(conn)
            _forbid_spans(monkeypatch)
            h = conn.execute_async(STAR_SQL)
            assert sorted(h.result().fetchall()) == STAR_ANSWER
            assert h._task.trace is None

    def test_pallas_query_records_kernel_and_scan_spans(self, wh_dir):
        with db.connect(wh_dir, engine="pallas", **TRACED) as conn:
            _load_star(conn)
            h = conn.execute_async(STAR_SQL)
            assert sorted(h.result().fetchall()) == STAR_ANSWER
            trace = h._task.trace
            names = {name for name, *_rest in trace._spans}
            for span in ("kernel.filter_eval", "kernel.key_lookup",
                         "llap.read", "scan.io_wait", "sched:worker_wait"):
                assert span in names, (span, sorted(names))
            summ = trace.summary()
            kernels = summ["kernels"]
            # the dispatch counts now derive from the round-trip spans
            assert summ["kernel_dispatches"] == {
                k: v["calls"] for k, v in kernels.items()}
            assert kernels["key_lookup[pallas]"]["calls"] >= 1
            assert all(v["mean_us"] > 0 and v["h2d_bytes"] > 0
                       for v in kernels.values())
            assert summ["kernel_h2d_bytes"] == sum(
                v["h2d_bytes"] for v in kernels.values())
            assert summ["spans_ms"]["llap.read"] > 0
            # no first-dispatch point events any more
            assert not [e for e in summ["events"] if e["cat"] == "kernel"]
            assert validate_chrome_trace(trace.to_chrome()) == []

    def test_h2d_bytes_of_a_key_lookup_call(self):
        from repro.core.runtime.exec import ExecContext

        ctx = ExecContext(None, None, config={"engine": "pallas"})
        ctx.trace = QueryTrace("q1")
        dictionary = np.arange(5, dtype=np.float32)
        probe = np.arange(1000, dtype=np.float32)
        ctx.kernel_call("key_lookup", dictionary, probe)
        # both operands padded to the 1024-row bucket, float32
        summ = ctx.trace.summary()
        assert summ["kernel_h2d_bytes"] == 2 * 1024 * 4
        assert summ["kernels"]["key_lookup[pallas]"]["calls"] == 1
        # bytes noted with no kernel span open go nowhere
        ctx.trace = None
        ctx.kernel_call("key_lookup", dictionary, probe)
        assert summ["kernel_h2d_bytes"] == 8192

    def test_spans_land_in_the_profiler_trace(self, wh_dir, tmp_path):
        import jax
        from jax.profiler import ProfileData

        with db.connect(wh_dir, engine="pallas", **TRACED) as conn:
            _load_star(conn)
            conn.execute(STAR_SQL).fetchall()  # compile outside the trace
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("test.window"):
                    h = conn.execute_async(STAR_SQL.replace("> 10", "> 11"))
                    h.result().fetchall()
            finally:
                jax.profiler.stop_trace()
            spans = h._task.trace._spans
        [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
        host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events]
        [(w0, w1)] = [(s, e) for n, s, e in host if n == "test.window"]
        for name in ("kernel.filter_eval", "kernel.key_lookup",
                     "scan.io_wait", "stage:execute"):
            traced = [ev for ev in host if ev[0] == name]
            assert traced, name
            assert all(w0 <= s <= e <= w1 for _n, s, e in traced), name
            # one profiler event per live span of the query
            assert len(traced) == sum(1 for s in spans if s[0] == name)
        assert not [n for n, *_t in host if n.startswith("bench.")]


class TestAdmissionSpans:
    def test_worker_and_admission_waits_make_up_queue_wait(self, tmp_path):
        from repro.core.session import Warehouse

        wh = Warehouse(str(tmp_path / "wh"), query_workers=1)
        with db.connect(warehouse=wh, debug_vertex_delay_s=0.05,
                        result_cache=False, **TRACED) as conn:
            _load_events(conn)
            handles = [conn.execute_async(
                f"SELECT grp, COUNT(*) FROM ev WHERE k > {i} GROUP BY grp")
                for i in range(3)]
            for h in handles:
                h.result().fetchall()
            for i, h in enumerate(handles):
                summ = h._task.trace.summary()
                worker = summ["spans_ms"]["sched:worker_wait"]
                admission = summ["spans_ms"]["wlm:admission_wait"]
                stages = summ["stages_ms"]
                # between the two waits the worker parses, binds and probes
                # the result cache; queue_wait_ms counts those stages too
                pre = stages["parse"] + stages["bind"] + stages["cache_probe"]
                queue = h.poll()["queue_wait_ms"]
                assert abs(worker + pre + admission - queue) < 1.0, (
                    i, worker, pre, admission, queue)
                assert worker + admission <= queue + 1.0
                if i:  # queued behind the one worker
                    assert worker > 40.0, (i, worker)
        wh.close()
