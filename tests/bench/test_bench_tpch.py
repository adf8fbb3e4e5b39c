"""The cell ``tpch-sf1.power`` rehearsed on the CPU at about scale factor
0.002: its result line, ``correct`` true on the program's answers, and
false with the control's (every SUM in float32) in their place or with half
of every scan dropped."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402
from bench.harness import main, resolve_cell  # noqa: E402

CELL = "tpch-sf1.power"
SEED = 2**31 + 23  # more than 32 signed bits hold


def _argv(trace=0, seed=SEED):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--rehearse", "--rows", "12000"]


def _last_line(text):
    return json.loads(text.strip().splitlines()[-1])


def test_cell_resolves_to_the_tpch_dataset():
    cell = resolve_cell(ROOT, CELL)
    assert cell.dataset.__name__.endswith("tpch")
    assert cell.traffic["queries"][:3] == ["q14", "q2", "q9"]
    assert len(cell.traffic["queries"]) == 22
    assert set(cell.readers) == {"key_lookup_roofline",
                                 "key_lookup_device_share", "plan_ms.tpch"}
    assert cell.config["limits"]["rel_gap_max"] == 1e-9


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, capsys):
    assert main(_argv(trace)) == 0
    out = _last_line(capsys.readouterr().out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["answers_compared"]["value"] >= 1
    assert out["checks"]["rel_gap_max"]["limit"] == 1e-9
    if trace:
        # no device trace off the chip: the kernel readers find nothing
        assert set(out["metrics"]) == {"plan_ms.tpch"}
    else:
        assert {"qps", "latency_p50_s", "setup_s"} == set(out["metrics"])


def test_control_in_the_programs_place_is_not_correct(monkeypatch, capsys):
    """The control answers in the program's place: its float32 sums miss
    the 1e-9 limit on Q14's ratio, the first query of the stream."""
    check = harness._check

    def with_control_answers(tables, traffic, records, config):
        done = [r for r in records if "error" not in r]
        answers = {r["sql"]: r["rows"] for r in control.control_records(
            traffic.dataset, tables, sorted({r["sql"] for r in done}))}
        for r in done:
            r["rows"] = answers[r["sql"]]
        return check(tables, traffic, records, config)

    monkeypatch.setattr(harness, "_check", with_control_answers)
    assert main(_argv(seed=SEED + 2)) == 0
    out = _last_line(capsys.readouterr().out)
    assert out["correct"] is False
    assert out["checks"]["rel_gap_max"]["value"] > 1e-9
    assert out["checks"]["unanswered"]["value"] == 0


def test_half_of_every_scan_is_not_correct(monkeypatch, capsys):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.runtime.exec import Executor

    stream = Executor._stream_scan

    def half(self, node):
        for b in stream(self, node):
            yield b.slice(0, (b.num_rows + 1) // 2)

    monkeypatch.setattr(Executor, "_stream_scan", half)
    assert main(_argv(seed=SEED + 1)) == 0
    out = _last_line(capsys.readouterr().out)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["values_mismatched"]["value"] > 0 or \
        checks["rel_gap_max"]["value"] > 1e-9
