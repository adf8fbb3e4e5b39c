"""Each benchmark cell rehearsed on the CPU at a small scale: the result
line's keys, the device it names, and the check that decides ``correct``
failing when the timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import main, resolve_cell  # noqa: E402

REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}
ALLOWED = REQUIRED | {"breakdown", "rehearsal", "checks"}
SEED = 2**31 + 17  # more than 32 signed bits hold
CELLS = ("ssb-sf1.power", "ssb-sf0.1.dashboard")


def _argv(cell, trace=0, seed=SEED):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--rehearse", "--rows", "9000"]


def _last_line(text):
    return json.loads(text.strip().splitlines()[-1])


def _check_line(out, cell, trace):
    assert REQUIRED <= set(out) <= ALLOWED
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 1
    assert "busy_s" not in out["device"]  # no device numbers off the chip
    spec = resolve_cell(ROOT, cell)
    want = {m["name"] for m in (spec.per_layer if trace else spec.end_to_end)}
    assert set(out["metrics"]) <= want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert out["attempted"] >= 1
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_command_line_rehearsal_prints_the_contract_line():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", *_argv("ssb-sf1.power")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = _last_line(proc.stdout)
    _check_line(out, "ssb-sf1.power", 0)
    assert out["correct"] is True
    assert {"qps", "latency_p50_s", "setup_s"} == set(out["metrics"])
    # the numbers compared are the last lines on standard error
    tail = proc.stderr.strip().splitlines()[-len(out["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_no_tpu_fails_without_a_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ssb-sf1.power",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_fail_without_a_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests/bench", tmp_path / "tests/bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", *_argv("ssb-sf1.power")],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell, trace, capsys):
    assert main(_argv(cell, trace)) == 0
    out = _last_line(capsys.readouterr().out)
    _check_line(out, cell, trace)
    assert out["correct"] is True and out["failed"] == 0
    if trace:
        assert "plan_ms" in out["metrics"]
        assert "kernel_calls_per_query" in out["metrics"]
    else:
        assert out["metrics"]["qps"]["value"] > 0


def _drop_one_code(fn):
    def broken(sorted_vals, probe):
        codes = np.array(fn(sorted_vals, probe))
        if len(codes):
            codes[0] = -1
        return codes
    return broken


def _half_of_each_scan(stream):
    def broken(self, node):
        for b in stream(self, node):
            yield b.slice(0, (b.num_rows + 1) // 2)
    return broken


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "half_the_batch"])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch, capsys):
    """An answer altered where it is produced (a join-key lookup returns a
    miss for one row of every call), or half of every scanned batch left
    out: the run completes, and ``correct`` reads false."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.runtime.exec import Executor
    from repro.kernels import registry

    if fault == "answer_altered":
        fn = registry.resolve("key_lookup", "pallas")
        registry.register("key_lookup", "pallas", _drop_one_code(fn))
    else:
        fn = None
        monkeypatch.setattr(Executor, "_stream_scan",
                            _half_of_each_scan(Executor._stream_scan))
    try:
        assert main(_argv(cell, 0, seed=SEED + 1)) == 0
    finally:
        if fn is not None:
            registry.register("key_lookup", "pallas", fn)
    out = _last_line(capsys.readouterr().out)
    assert out["correct"] is False
    assert out["checks"]["values_mismatched"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell, monkeypatch,
                                                      capsys):
    """The control (every SUM in float32) answers in the program's place:
    the run's answers are swapped for the control's before the harness's
    own check, and ``correct`` reads false."""
    from bench import control, harness

    check = harness._check

    def with_control_answers(tables, traffic, records, config):
        done = [r for r in records if "error" not in r]
        answers = {r["sql"]: r["rows"] for r in control.control_records(
            traffic.dataset, tables, sorted({r["sql"] for r in done}))}
        for r in done:
            r["rows"] = answers[r["sql"]]
        return check(tables, traffic, records, config)

    monkeypatch.setattr(harness, "_check", with_control_answers)
    assert main(_argv(cell, 0, seed=SEED + 2)) == 0
    out = _last_line(capsys.readouterr().out)
    assert out["correct"] is False
    assert out["checks"]["values_mismatched"]["value"] > 0
    assert out["checks"]["unanswered"]["value"] == 0
