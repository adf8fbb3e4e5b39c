"""The benchmark's yardstick: trace reduction, kernel bytes, peaks, the
reference comparison and its control, and discovery of cells by name."""
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import kernel_bytes, peaks, profiling, reference, ssb  # noqa: E402
from bench.harness import resolve_cell  # noqa: E402

MS = 1_000_000  # ns


def _trace():
    """A small trace in the reduction's input form: a 100 ms window, device
    ops busy for 30 ms of it, two kernel programs, and host annotations."""
    host = [
        ("python", [(profiling.WINDOW, 0, 100 * MS)]),
        ("worker-1", [(profiling.KERNEL + "key_lookup", 1 * MS, 22 * MS),
                      (profiling.KERNEL + "bloom_probe", 70 * MS, 75 * MS)]),
    ]
    device = [
        ("XLA Modules", [
            ("jit__key_lookup_jit(12)", 10 * MS, 20 * MS),
            ("jit__bloom_probe_jit(7)", 71 * MS, 74 * MS),
            ("jit_other(1)", 95 * MS, 110 * MS),  # clipped to the window
        ]),
        ("XLA Ops", [
            ("%_key_lookup_jit.1 = s32[1024]{0:T(1024)} custom-call("
             "f32[8,128]{1,0:T(8,128)} %bitcast.1)", 10 * MS, 20 * MS),
            ("bloom_kernel", 71 * MS, 74 * MS),
            ("fusion.1", 40 * MS, 52 * MS),
            ("fusion.1", 50 * MS, 55 * MS),  # overlaps the last one
            ("copy", 95 * MS, 110 * MS),
        ]),
    ]
    return [("/host:CPU", host), ("/device:TPU:0", device),
            ("/host:metadata", [])]


QUERIES = [("q1.1", -0.05, 0.06), ("q2.1", 0.055, 0.2)]


def test_reduction_idle_share_and_kernel_time():
    red = profiling.reduce_planes(_trace())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [10,20] + [40,55] + [71,74] + [95,100] = 33 ms
    assert red["busy_s"] == pytest.approx(0.033)
    assert red["kernel_s"] == pytest.approx(
        {"key_lookup": 0.010, "bloom_probe": 0.003})
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.017)
    assert ops["copy"] == pytest.approx(0.005)
    assert ops["_key_lookup_jit.1 = s32[1024]"] == pytest.approx(0.010)


def test_reduction_attributes_gaps_to_host_activity():
    red = profiling.reduce_planes(_trace(), QUERIES)
    gaps = red["idle_gaps"]
    # gaps: [0,10] [20,40] [55,71] [74,95], longest first
    assert [round(s * 1e3, 3) for _n, s in gaps] == [21.0, 20.0, 16.0, 10.0]
    names = dict((round(s * 1e3), n) for n, s in gaps)
    assert names[10] == "host side of a key_lookup call"
    assert names[20] == "host between kernel calls: q1.1"
    assert names[16] == "host between kernel calls: q1.1,q2.1"
    assert names[21] == "host between kernel calls: q2.1"


def test_reduction_without_window_or_device():
    assert profiling.reduce_planes([("/host:CPU", [])]) == {}
    host_only = [("/host:CPU", [("python", [(profiling.WINDOW, 0, MS)])])]
    assert profiling.reduce_planes(host_only) == {"window_s": 0.001}


@pytest.mark.parametrize("kernel,args,expected", [
    ("filter_eval", ([np.zeros(1000, np.float32)] * 2, (0, 1), (1.0, 2.0)),
     1000 * 4 * 2 + 1000),
    ("key_lookup", (np.zeros(400, np.float32), np.zeros(1024, np.float32)),
     400 * 4 + 1024 * 8),
    ("bloom_probe", (np.zeros(8192, np.uint32), np.zeros(8192, np.uint32),
                     np.zeros(64, np.uint32), 6, 2048),
     8192 * 8 + 64 * 4 + 8192),
    ("hash_group", (np.zeros(1024, np.int32), np.zeros(1024, np.float32),
                    7), 1024 * 8 + 7 * 8),
    ("hash_group_minmax", (np.zeros(10, np.int32),
                           np.zeros(10, np.float32), 3), 10 * 8 + 3 * 8),
    ("hash_partition", ((np.zeros(100, np.float32),) * 3, 4),
     100 * 12 + 100 * 4),
])
def test_kernel_bytes_against_shapes(kernel, args, expected):
    assert kernel_bytes.BYTES[kernel](*args) == expected


def test_kernel_bytes_cover_every_traced_program():
    assert set(kernel_bytes.BYTES) == set(profiling.KERNEL_PROGRAMS)


def test_peak_table_refuses_unknown_kind():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")


DIMS = {"customer": 1000, "supplier": 200, "part": 1000}


def _tables(seed=5, rows=3000):
    return ssb.generate(seed, {"lineorder_rows": rows, "dimension_rows": DIMS})


def test_generator_is_a_function_of_the_seed():
    a, b = _tables(2**31 + 12345), _tables(2**31 + 12345)
    for t in a:
        for c in a[t]:
            assert np.array_equal(a[t][c], b[t][c])
    small = ssb.head(a, 100)
    assert len(small["lineorder"]["lo_orderkey"]) == 100
    assert np.array_equal(small["customer"]["c_region"],
                          a["customer"]["c_region"])


def test_generator_keeps_the_specification_shapes():
    """SSB's columns, domains and sizes: 17 lineorder columns, 2,556
    yyyymmdd dates, 250 cities, 25 categories, 1,000 brands, orders of 1 to
    7 lines, and every constant of the published queries in its domain."""
    t = ssb.generate(3, {"lineorder_rows": 20_000, "dimension_rows": {
        "customer": 30_000, "supplier": 2000, "part": 200_000}})
    lo, d, p, c = t["lineorder"], t["date"], t["part"], t["customer"]
    assert len(lo) == 17 and len(d) == 17 and len(p) == 9 and len(c) == 8
    assert {k: len(next(iter(v.values()))) for k, v in t.items()} == {
        "date": 2556, "customer": 30_000, "supplier": 2000, "part": 200_000,
        "lineorder": 20_000}
    assert d["d_datekey"][0] == 19920101 and d["d_datekey"][-1] == 19981230
    assert set(lo["lo_orderdate"]) <= set(d["d_datekey"])
    assert set(lo["lo_commitdate"]) <= set(d["d_datekey"])
    assert lo["lo_discount"].min() == 0 and lo["lo_discount"].max() == 10
    assert lo["lo_quantity"].min() == 1 and lo["lo_quantity"].max() == 50
    assert lo["lo_tax"].max() == 8 and lo["lo_linenumber"].max() == 7
    assert len(np.unique(lo["lo_orderkey"])) < len(lo["lo_orderkey"]) / 3
    assert np.array_equal(lo["lo_revenue"],
                          lo["lo_extendedprice"] * (100 - lo["lo_discount"])
                          // 100)
    assert len(set(c["c_city"])) == 250 and len(set(c["c_nation"])) == 25
    assert len(set(p["p_category"])) == 25 and len(set(p["p_brand1"])) == 1000
    for value, domain in [("UNITED KI1", c["c_city"]),
                          ("UNITED KI5", t["supplier"]["s_city"]),
                          ("MFGR#2239", p["p_brand1"]),
                          ("MFGR#14", p["p_category"]), ("MFGR#1", p["p_mfgr"]),
                          ("Dec1997", d["d_yearmonth"]),
                          ("UNITED STATES", c["c_nation"])]:
        assert value in set(domain)
        assert value in " ".join(ssb.PUBLISHED.values())


def test_compare_reads_gap_and_mismatches():
    want = [(1993, "BRAND_1", 1000.0), (1994, "BRAND_1", 2000.0)]
    got = [(np.int64(1994), "BRAND_1", np.float64(2000.0000002)),
           (np.int64(1993), "BRAND_1", np.float64(1000.0))]
    gap, bad = reference.compare(got, want)
    assert bad == 0 and gap == pytest.approx(1e-10, rel=1e-3)
    assert reference.compare(got[:1], want) == (0.0, 2)
    assert reference.compare([(1993, "BRAND_2", 1000.0)] + got[:1],
                             want)[1] == 1
    assert reference.compare([(float("nan"),)], [(None,)]) == (0.0, 0)
    # an integer sum is exact: a float that rounds it is a mismatch
    assert reference.compare([(16777217.0,)], [(16777217,)]) == (0.0, 0)
    assert reference.compare([(float(np.float32(16777217)),)],
                             [(16777217,)]) == (0.0, 1)


def test_control_fails_the_limit_where_the_reference_passes():
    """The float32 control, on the published queries at a test's size,
    reads mismatched values over the configurations' limit of 0; the
    reference against itself reads none."""
    limits = json.loads((ROOT / "bench/configs/ssb-sf1.json").read_text())[
        "limits"]
    tables = _tables(rows=40_000)
    ref = reference.sqlite_reference(tables, ssb.KEYS, control=True)
    mismatched = 0
    for sql in ssb.PUBLISHED.values():
        want = ref.execute(sql).fetchall()
        assert reference.compare(want, want) == (0.0, 0)
        got = ref.execute(reference.control_sql(sql)).fetchall()
        assert len(got) == len(want)
        mismatched += reference.compare(got, want)[1]
    assert mismatched > limits["values_mismatched"]


def test_reference_loads_only_what_the_statements_name():
    tables = _tables(rows=500)
    sql = ssb.PUBLISHED["q1.1"]
    db = reference.sqlite_reference(tables, ssb.KEYS, sqls=[sql])
    names = {r[0] for r in db.execute(
        "select name from sqlite_master where type = 'table'")}
    assert names == {"lineorder", "date"}
    cols = [r[1] for r in db.execute("pragma table_info(lineorder)")]
    assert cols == ["lo_orderdate", "lo_quantity", "lo_extendedprice",
                    "lo_discount"]
    full = reference.sqlite_reference(tables, ssb.KEYS)
    assert db.execute(sql).fetchall() == full.execute(sql).fetchall()


def test_new_cell_is_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as files,
    and named in BENCHMARK.json, make a cell; no other file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/ssb-sf0.1.json").read_text())
    cfg["lineorder_rows"] = 1_200_000
    (tmp_path / "bench/configs/ssb-sf0.2.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/adhoc.json").write_text(json.dumps({
        "clients": 32, "published_share": 0.0, "published_order": "zipf",
        "zipf_alpha": 1.1, "fresh_pool_per_s": 2}))
    (tmp_path / "bench/metrics/fresh_share.py").write_text(
        "def read(run):\n"
        "    return 100.0 * sum(r['label'].startswith('fresh:')\n"
        "                       for r in run.done) / len(run.done)\n")
    spec["configs"].append({"name": "ssb-sf0.2", "source": "x",
                            "file": "bench/configs/ssb-sf0.2.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "ssb-sf0.2.adhoc", "config": "ssb-sf0.2",
                              "traffic": "adhoc", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "fresh_share", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "core/serving", "moves": "qps",
                              "workloads": ["ssb-sf0.2.adhoc"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = resolve_cell(tmp_path, "ssb-sf0.2.adhoc")
    assert cell.config["lineorder_rows"] == 1_200_000
    assert cell.traffic["published_share"] == 0.0
    assert [m["name"] for m in cell.end_to_end] == [
        "qps", "latency_p50_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "fresh_share" in names and "compiles_in_window.power" not in names
    run = SimpleNamespace(done=[{"label": "fresh:q1.1"}, {"label": "q2.1"}],
                          counters={}, profile=None, peaks=None)
    assert cell.readers["fresh_share"](run) == 50.0
    assert set(cell.readers) == {"fresh_share"}
    # the cells already there resolve as before
    assert resolve_cell(tmp_path, "ssb-sf1.power").traffic["clients"] == 1


def test_traffic_sends_the_same_requests_on_every_seed():
    """Every seed sends the same published queries in the same numbers and
    the same share of fresh ones, from each client's first requests on."""
    from bench.loadgen import Traffic

    spec = json.loads((ROOT / "bench/traffic/dashboard.json").read_text())
    sent = []
    for seed in (5, 2**31 + 12345):
        traffic = Traffic(spec, ssb, seed, 51)
        firsts = [[label for label, _sql in
                   (next(s) for _ in range(10))]
                  for s in map(traffic.stream, range(spec["clients"]))]
        assert all(sum(x.startswith("fresh:") for x in f) == 4
                   for f in firsts)
        sent.append(sorted(x for f in firsts for x in f
                           if not x.startswith("fresh:")))
    assert sent[0] == sent[1]
    assert len(set(sent[0])) > 3  # Zipf over the ranking, not one query
