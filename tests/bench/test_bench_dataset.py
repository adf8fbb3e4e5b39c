"""A dataset that is not SSB, added as files alone: a root of its own with
a ``BENCHMARK.json``, a configuration naming the toy dataset module, a
traffic mix and a metric reader, rehearsed on the CPU through the harness
as it stands."""
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CELL = "toy.mix"


@pytest.fixture
def toy_root(tmp_path):
    """A benchmark root whose one cell runs the toy dataset."""
    root = tmp_path / "root"
    (root / "bench/configs").mkdir(parents=True)
    (root / "bench/traffic").mkdir()
    (root / "bench/metrics").mkdir()
    shutil.copy(Path(__file__).with_name("toy_dataset.py"),
                root / "bench/toy.py")
    shutil.copy(ROOT / "bench/metrics/plan_ms.py", root / "bench/metrics")
    (root / "bench/configs/toy.json").write_text(json.dumps({
        "name": "toy", "dataset": "toy", "sale_rows": 50_000,
        "store_rows": 60,
        "warehouse": {"llap_cache_bytes": 1 << 26, "query_workers": 4},
        "session": {"engine": "pallas", "shuffle.partitions": 1},
        "limits": {"unanswered": 0, "values_mismatched": 0}}))
    (root / "bench/traffic/mix.json").write_text(json.dumps({
        "clients": 2, "published_share": 0.5, "published_order": "rotation",
        "fresh_pool_per_s": 3, "fresh_pool_seed": 1, "reference_fresh": 2,
        "session": {"result_cache": False, "serving.result_cache": False},
        "warm_result_cache": False}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": CELL, "config": "toy", "traffic": "mix",
                       "chips": 1}],
        "end_to_end": [{"name": "qps", "unit": "queries/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "plan_ms", "unit": "ms"}]}))
    return root


def _rehearse(root, tmp_path, capsys):
    """One rehearsal of the toy cell through ``resolve_cell``, ``_run`` and
    ``_report``; its result line."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    cell = harness.resolve_cell(root, CELL)
    args = harness.parse_args(["--workload", CELL, "--seed", str(2**31 + 3),
                               "--seconds", "1", "--rehearse", "--rows",
                               "3000"])
    run = harness._run(cell, args, jax, None, tmp_path / "run",
                       time.perf_counter())
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert harness._report(cell, args, run, device) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_toy_dataset_resolves_from_its_own_root(toy_root):
    cell = harness.resolve_cell(toy_root, CELL)
    assert cell.dataset.KEYS == {"store": "st_key"}
    assert set(cell.readers) == {"plan_ms"}
    tables = cell.dataset.generate(7, cell.config, 100)
    assert set(tables) == {"sale", "store"}
    assert len(tables["sale"]["sa_qty"]) == 100


def test_toy_dataset_rehearses_correct(toy_root, tmp_path, capsys):
    out = _rehearse(toy_root, tmp_path, capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["answers_compared"]["value"] >= 3
    assert {"qps", "setup_s"} == set(out["metrics"])


def test_toy_dataset_planted_wrong_answer_is_not_correct(
        toy_root, tmp_path, capsys, monkeypatch):
    """Half of every scanned batch left out of the timed path: the toy's
    answers differ from its reference and ``correct`` reads false."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.runtime.exec import Executor

    stream = Executor._stream_scan

    def half(self, node):
        for b in stream(self, node):
            yield b.slice(0, (b.num_rows + 1) // 2)

    monkeypatch.setattr(Executor, "_stream_scan", half)
    out = _rehearse(toy_root, tmp_path, capsys)
    assert out["correct"] is False
    assert out["checks"]["values_mismatched"]["value"] > 0
