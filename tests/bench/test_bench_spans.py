"""The reduction of the device's idle time under the program's own spans
(``bench/spans.py``), and the older reduction's indifference to them."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import profiling, spans  # noqa: E402

MS = 1_000_000  # ns


def _planes(host_lines):
    """A 100 ms window whose device is busy over [10, 20] and [60, 70] ms,
    so idle over [0, 10], [20, 60] and [70, 100]: 80 ms."""
    host = [("python", [(profiling.WINDOW, 0, 100 * MS)])] + host_lines
    device = [("XLA Ops", [("fusion.1", 10 * MS, 20 * MS),
                           ("fusion.2", 60 * MS, 70 * MS)])]
    return [("/host:CPU", host), ("/device:TPU:0", device)]


def test_idle_under_each_span_name_with_threads_overlapping():
    red = spans.idle_spans(_planes([
        # two threads in key_lookup at once over [25, 40]: counted once
        ("vertex-1", [("kernel.key_lookup", 5 * MS, 40 * MS),
                      ("llap.read", 75 * MS, 80 * MS)]),
        ("vertex-2", [("kernel.key_lookup", 25 * MS, 50 * MS),
                      ("kernel.bloom_probe", 55 * MS, 65 * MS)]),
        # an I/O thread: a read nested in nothing, past the window's end
        ("llap-io-0", [("llap.read", 78 * MS, 120 * MS),
                       ("scan.io_wait", -5 * MS, 2 * MS)]),
        # the benchmark's own annotations and XLA's are not program spans
        ("vertex-3", [(profiling.KERNEL + "key_lookup", 0, 100 * MS),
                      ("PjitFunction(_key_lookup_jit)", 0, 100 * MS)]),
    ]))
    assert red["idle_s"] == pytest.approx(0.080)
    by = dict(red["spans"])
    # key_lookup over [5, 50]: idle [5, 10] and [20, 50] = 35 ms
    assert by["kernel.key_lookup"] == pytest.approx(0.035)
    # bloom_probe over [55, 65]: idle [55, 60] = 5 ms
    assert by["kernel.bloom_probe"] == pytest.approx(0.005)
    # llap.read over [75, 80] and [78, 100]: union [75, 100] = 25 ms
    assert by["llap.read"] == pytest.approx(0.025)
    assert by["scan.io_wait"] == pytest.approx(0.002)
    assert [n for n, _t in red["spans"]] == [
        "kernel.key_lookup", "llap.read", "kernel.bloom_probe",
        "scan.io_wait"]
    # any kernel: [5, 50] and [55, 65] -> idle 35 + 5 ms
    assert red["kernel_calls_s"] == pytest.approx(0.040)
    # under no program span: [2, 5], [50, 55] and [70, 75] = 13 ms
    assert red["uncovered_s"] == pytest.approx(0.013)


def test_top_keeps_the_longest_names():
    host = [("t", [(f"stage:s{i}", 20 * MS, (21 + i) * MS)
                   for i in range(12)])]
    red = spans.idle_spans(_planes(host), top=3)
    assert [n for n, _t in red["spans"]] == ["stage:s11", "stage:s10",
                                               "stage:s9"]


def test_no_window_or_no_device_op_gives_nothing():
    assert spans.idle_spans([("/host:CPU", [("t", [("kernel.x", 0, 5)])])]) \
        == {}
    planes = _planes([])
    planes[1] = ("/device:TPU:0", [("XLA Ops", [])])
    assert spans.idle_spans(planes) == {}


@pytest.mark.parametrize("queries", [(), (("q1.1", 0.0, 0.05),
                                          ("q2.1", 0.05, 0.1))])
def test_reduce_planes_ignores_program_spans(queries):
    bench_only = [("vertex-1", [(profiling.KERNEL + "key_lookup",
                                 22 * MS, 58 * MS)])]
    program = [("vertex-1", [(profiling.KERNEL + "key_lookup",
                              22 * MS, 58 * MS),
                             ("kernel.key_lookup", 23 * MS, 57 * MS),
                             ("kernel.bloom_probe", 72 * MS, 99 * MS)]),
               ("llap-io-0", [("llap.read", 1 * MS, 9 * MS),
                              ("scan.io_wait", 30 * MS, 90 * MS)])]
    before = profiling.reduce_planes(_planes(bench_only), queries)
    after = profiling.reduce_planes(_planes(program), queries)
    assert before and after == before
