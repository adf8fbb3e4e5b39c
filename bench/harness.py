"""One run of one benchmark cell: set-up, measured window, check, report.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness reads ``configs/<config>.json``, ``traffic/<traffic>.json``, the
configuration's dataset module ``<dataset>.py`` (its tables, statements
and reference keys; SSB's is ``ssb.py``) and, for each per-layer metric,
``metrics/<metric>.py``, all found by name.  It holds nothing that belongs
to one cell or one dataset.

A run:

1. checks the device: a TPU with the chips the cell asks for, or, with
   ``--rehearse``, the CPU at a small scale (never reporting a TPU);
2. set-up (``setup_s``, from process start): JAX and the persistent compile
   cache in the checkout, the tables drawn from ``--seed`` and inserted
   through the warehouse's own ACID path, and warm-up: every statement the
   window can send, run on the dataset's small copy of the same tables, so
   that every kernel program the window reaches is compiled or loaded from
   the cache; where the mix keeps a warm result cache, the
   published queries also run once on the real tables;
3. the window: the mix's clients through ``repro.api`` for ``--seconds``,
   then the queries in flight; with ``--trace 1`` the warehouse traces
   every query, and the profiler traces a few seconds of the window;
4. the check, once the window has closed and the warehouse is shut: each
   answer checked against the sqlite3 reference over the same rows;
5. the report: the numbers compared, each beside its limit, as the last
   lines on standard error, and one JSON line last on standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from . import loadgen

ROOT = Path(__file__).resolve().parents[1]
STRIPE_ROWS = 8192  # the warehouse's stripe length: the warm copy keeps it


def resolve_cell(root: Path, name: str) -> SimpleNamespace:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its configuration,
    its configuration's dataset module, traffic mix, metric entries and
    metric readers loaded by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    bench = root / "bench"
    per_layer = mine(spec["per_layer"])
    config = json.loads(
        (bench / "configs" / f"{cell['config']}.json").read_text())
    return SimpleNamespace(
        name=name,
        chips=int(cell["chips"]),
        config=config,
        dataset=_load_module(bench / f"{config['dataset']}.py",
                             "bench_dataset_"),
        traffic=json.loads(
            (bench / "traffic" / f"{cell['traffic']}.json").read_text()),
        end_to_end=mine(spec["end_to_end"]),
        per_layer=per_layer,
        readers={m["name"]: load_reader(bench / "metrics" / f"{m['name']}.py")
                 for m in per_layer},
    )


def load_reader(path: Path):
    """The ``read(run)`` function of one per-layer metric's file."""
    return _load_module(path, "bench_metric_").read


def _load_module(path: Path, prefix: str):
    """The Python file at ``path``, loaded by path as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at --rows fact rows; never "
                         "reports a TPU")
    ap.add_argument("--rows", type=int, default=12_000,
                    help="the dataset's fact rows in a rehearsal, which "
                         "also cuts its other tables as the dataset says")
    return ap.parse_args(argv)


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    cell = resolve_cell(ROOT, args.workload)
    if not args.rehearse:
        # the checkout's fixed cache directory, whatever the environment
        # says: only the first run of a cell in a checkout compiles
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse and platform == "tpu":
        print("a rehearsal runs on the CPU; this host's JAX found a TPU",
              file=sys.stderr)
        return 2
    if not args.rehearse and (platform != "tpu" or len(devices) < cell.chips):
        print(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}", file=sys.stderr, flush=True)
    peaks = None
    if not args.rehearse:
        from .peaks import peak

        peaks = peak(device["kind"])
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    sys.path.insert(0, str(ROOT / "src"))
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        run = _run(cell, args, jax, peaks, Path(tmp), t_start)
    return _report(cell, args, run, device)


def _run(cell, args, jax, peaks, tmp: Path, t_start):
    import repro.api as db
    from repro.core.session import Warehouse
    from repro.kernels import registry

    from . import kernel_bytes, profiling

    compiles = profiling.CompileCounter(jax)
    config, traffic_spec, data = cell.config, cell.traffic, cell.dataset
    phases = Phases(t_start, compiles)
    tables = data.generate(args.seed, config,
                           args.rows if args.rehearse else None)
    phases.done("start_and_generate")
    session = {**config["session"], **traffic_spec.get("session", {})}
    traffic = loadgen.Traffic(traffic_spec, data, args.seed, args.seconds)
    statements = traffic.statements()

    # warm-up on a small copy of the same tables: every statement the
    # window can send, then every kernel shape their plans can reach
    small = Warehouse(str(tmp / "warm"), **config["warehouse"])
    data.load(small, data.warm_copy(tables, STRIPE_ROWS))
    phases.done("load_small_copy")
    with _filters_seen(registry) as filters:
        _run_all(db.connect(warehouse=small, **session),
                 [sql for _l, sql in statements])
    small.close()
    phases.done(f"warm_{len(statements)}_statements")
    shapes = _warm_shapes(registry, filters, data.warm_bound(tables))
    phases.done(f"warm_{shapes}_kernel_shapes")

    wh = Warehouse(str(tmp / "wh"), **config["warehouse"])
    data.load(wh, tables)
    phases.done("load")
    if traffic_spec.get("warm_result_cache"):
        _run_all(db.connect(warehouse=wh, **session),
                 [traffic.published[n] for n in traffic.names])
        phases.done("warm_result_cache")

    # set-up is the same with --trace 1; only the window differs
    profile = traffic_spec.get("profile") if args.trace and not \
        args.rehearse else None
    kcalls = (profiling.KernelCalls(jax, registry, kernel_bytes.BYTES)
              if profile else None)
    if args.trace:
        session["obs.tracing"] = True
    conns = [db.connect(warehouse=wh, **session)
             for _ in range(int(traffic_spec["clients"]))]

    def snapshot():
        counters = dict(registry.dispatch_counts())
        counters.update({f"llap.{k}": v for k, v in wh.llap.counters.items()})
        rc = wh.serving_stats()["result_cache"]
        counters.update({f"result_cache.{k}": v for k, v in rc.items()
                         if isinstance(v, (int, float))})
        counters["compiles"] = compiles.compiles
        return counters

    before = snapshot()
    trace_dir = tmp / "profile"
    traced = {}

    def during(t0):
        if not profile:
            return
        start = min(float(profile["start_s"]), 0.2 * args.seconds)
        length = min(float(profile["seconds"]), args.seconds - start)
        time.sleep(max(0.0, t0 + start - time.perf_counter()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host annotations, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        kcalls.active = True
        with jax.profiler.TraceAnnotation(profiling.WINDOW):
            traced["t0"] = time.perf_counter()
            time.sleep(length)
        kcalls.active = False
        jax.profiler.stop_trace()

    if profile:
        annotate = lambda label: jax.profiler.TraceAnnotation(  # noqa: E731
            profiling.QUERY + label)
    else:
        annotate = lambda label: contextlib.nullcontext()  # noqa: E731
    t0, records = loadgen.run_window(
        traffic, conns, args.seconds, annotate,
        on_done=_handle_reader(bool(args.trace)), during=during)
    setup_s = t0 - t_start
    after = snapshot()
    peak_bytes = memory_peak_bytes(jax)
    for c in conns:
        c.close()
    wh.close()

    reduction = None
    if profile:
        reduction = profiling.reduce_planes(
            profiling.read_xplane(profiling.find_xplane(str(trace_dir))),
            [(r["label"], r["t_submit"] - traced["t0"],
              r["t_done"] - traced["t0"]) for r in records])
    checks = _check(tables, traffic, records, config)
    done = [r for r in records if "error" not in r]
    t_last = max((r["t_done"] for r in records), default=t0)
    return SimpleNamespace(
        cell=cell.name, setup_s=setup_s, records=records, done=done,
        window_s=t_last - t0, counters={k: after.get(k, 0) - before.get(k, 0)
                                        for k in after},
        profile=reduction, peaks=peaks,
        kernel_bytes=dict(kcalls.bytes) if kcalls else {},
        memory_peak_bytes=peak_bytes, checks=checks,
        fresh_exhausted=traffic.fresh_exhausted, phases=phases.log,
    )


class Phases:
    """Seconds, compiles and compile-cache hits of each set-up phase."""

    def __init__(self, t_start, compiles):
        self.log, self._t, self._c = [], t_start, compiles
        self._n = (0, 0)

    def done(self, name):
        t, n = time.perf_counter(), (self._c.compiles, self._c.cache_hits)
        self.log.append(f"{name} {t - self._t:.3f}s compiles "
                        f"{n[0] - self._n[0]} cache_hits {n[1] - self._n[1]}")
        self._t, self._n = t, n


def _run_all(conn, sqls):
    """Run statements concurrently on one connection's warehouse; raise if
    any fails."""
    handles = [conn.execute_async(sql) for sql in sqls]
    for h in handles:
        h.result(loadgen.QUERY_TIMEOUT_S)
    conn.close()


@contextlib.contextmanager
def _filters_seen(registry):
    """Record the ``(columns, ops, literals)`` of every filter-kernel call
    made inside the block."""
    seen = set()
    fn = registry.resolve("filter_eval", "pallas")

    def record(columns, ops, lits):
        seen.add((len(columns), ops, lits))
        return fn(columns, ops, lits)

    registry.register("filter_eval", "pallas", record)
    try:
        yield seen
    finally:
        registry.register("filter_eval", "pallas", fn)


ROW_BUCKETS = (1024, 2048, 4096, 8192)  # a stripe holds at most 8192 rows
GROUP_BUCKETS = (128, 256, 512, 1024)  # groups of one morsel's partial


def _warm_shapes(registry, filters, most: int) -> int:
    """Compile, or load from the cache, every kernel program the window's
    plans can reach.

    The optimizer's plans depend on the fact table's size, so the small
    copy's plans can differ from the real ones: a runtime filter may shrink
    a scan before its predicate runs, or a dimension may be probed instead
    of built.  What a plan changes is the row bucket of a call, and which
    side a dictionary or bloom filter is built from; the keys of any build
    side are at most ``most`` (the dataset's ``warm_bound``: for SSB the
    largest dimension's rows), and a filter kernel only sees a build side's
    rows or a morsel.  So every filter seen is compiled at every row bucket
    up to ``most``'s, the dictionary lookup and bloom probe at every row
    bucket and every build size up to that bound, and the grouped sum,
    which a morsel whose values float32 holds exactly (or an empty one)
    reaches, at every morsel group bucket.
    Returns the number of shapes.
    """
    import numpy as np

    from repro.kernels.registry import bucket

    calls = [("filter_eval", ((np.zeros(rows, np.float32),) * ncols, ops,
                              lits))
             for rows in ROW_BUCKETS if rows <= bucket(most)
             for ncols, ops, lits in filters]
    for rows in ROW_BUCKETS:
        zeros = np.zeros(rows, np.float32)
        g = 1024
        while g <= bucket(most):
            calls.append(("key_lookup",
                          (np.arange(g, dtype=np.float32), zeros)))
            g *= 2
        h = np.zeros(rows, np.uint32)
        bits = 64  # BloomFilter's least size; ~8 bits a key, to a power of 2
        while bits < 16 * most:
            calls.append(("bloom_probe",
                          (h, h, np.zeros(bits // 32, np.uint32), 6, bits)))
            bits *= 2
        codes = np.zeros(rows, np.int32)
        calls += [("hash_group", (codes, zeros, groups))
                  for groups in GROUP_BUCKETS]
    for name, args in calls:
        registry.resolve(name, "pallas")(*args)
    return len(calls)


def _handle_reader(traced: bool):
    """What a run keeps from a finished query handle: its stage times and
    queue wait, and with tracing its vertices' exchange wait."""

    def on_done(rec, h):
        info = h.info
        rec["stage_ms"] = dict(info.get("stage_times_ms", {}))
        rec["cache_hit"] = bool(info.get("cache_hit", False))
        rec["queue_wait_ms"] = h.poll().get("queue_wait_ms")
        if traced and not rec["cache_hit"]:
            wait = total = 0.0
            for ev in h.trace()["traceEvents"]:
                if ev.get("cat") == "vertex" and ev.get("ph") == "B":
                    a = ev["args"]
                    wait += a["exchange_wait_ms"]
                    total += (a["compute_ms"] + a["exchange_wait_ms"]
                              + a["spill_io_ms"])
            rec["vertex_wait_ms"], rec["vertex_ms"] = wait, total

    return on_done


def memory_peak_bytes(jax) -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks, default=0))


def _check(tables, traffic, records, config) -> dict:
    """The numbers compared against the reference, each with its limit."""
    from .reference import compare, sqlite_reference

    data = traffic.dataset
    sent = {r["sql"] for r in records}
    checked = sorted(traffic.reference_sample(sent))
    ref = sqlite_reference(tables, data.KEYS, sqls=checked)
    want = {sql: ref.execute(data.reference_sql(sql)).fetchall()
            for sql in checked}
    ref.close()
    gap, mismatched, compared = 0.0, 0, 0
    for r in records:
        if "error" in r or r["sql"] not in want:
            continue
        g, m = compare(r["rows"], want[r["sql"]])
        gap, mismatched, compared = max(gap, g), mismatched + m, compared + 1
    limits = config["limits"]
    checks = {
        "answers_compared": {"value": compared, "limit": 1, "at_least": True},
        "unanswered": {"value": sum("error" in r for r in records),
                       "limit": limits["unanswered"]},
        "values_mismatched": {"value": mismatched,
                              "limit": limits["values_mismatched"]},
    }
    if "rel_gap_max" in limits:  # a configuration whose answers hold floats
        checks["rel_gap_max"] = {"value": gap, "limit": limits["rel_gap_max"]}
    return checks


def decide(checks) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] >= c["limit"] if c.get("at_least") else
               c["value"] <= c["limit"] for c in checks.values())


def _percentile(values, q):
    """The ``q``-th percentile, linear between order statistics."""
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def end_to_end(run) -> dict:
    lat = [r["t_done"] - r["t_submit"] for r in run.done]
    return {
        "qps": len(run.done) / run.window_s if run.window_s > 0 else None,
        "latency_p50_s": statistics.median(lat) if lat else None,
        "latency_p95_s": _percentile(lat, 95) if lat else None,
        "setup_s": run.setup_s,
    }


def _report(cell, args, run, device) -> int:
    failed = sum("error" in r for r in run.records)
    if args.trace:
        values = {m["name"]: cell.readers[m["name"]](run)
                  for m in cell.per_layer}
        entries = cell.per_layer
    else:
        values = end_to_end(run)
        entries = cell.end_to_end
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in entries if values.get(m["name"]) is not None}
    checks = run.checks
    correct = decide(checks)
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": correct, "attempted": len(run.records),
           "failed": failed, "metrics": metrics, "device": dev}
    if run.profile and "busy_s" in run.profile:
        dev["busy_s"] = run.profile["busy_s"]
        dev["window_s"] = run.profile["window_s"]
        out["breakdown"] = {"device_ops": run.profile["device_ops"],
                            "idle_gaps": run.profile["idle_gaps"]}
    if args.rehearse:
        out["rehearsal"] = True
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}

    for line in run.phases:
        print(f"setup {line}", file=sys.stderr)
    lat = sorted(r["t_done"] - r["t_submit"] for r in run.done)
    print(f"cell {cell.name} seed {args.seed} on {device['platform']} "
          f"{device['kind']} x{device['count']}: {len(run.done)} of "
          f"{len(run.records)} queries in {run.window_s:.3f} s; setup "
          f"{run.setup_s:.3f} s; latency min/max "
          f"{(lat or [0])[0]:.3f}/{(lat or [0])[-1]:.3f} s; compiles in "
          f"window {run.counters.get('compiles', 0)}; fresh pool exhausted "
          f"{run.fresh_exhausted}; kernel calls "
          f"{ {k: v for k, v in run.counters.items() if '[' in k and v} }",
          file=sys.stderr)
    by_label = {}
    for r in run.done:
        by_label.setdefault(r["label"], []).append(
            r["t_done"] - r["t_submit"])
    print("latency median s by query: " + ", ".join(
        f"{k} {statistics.median(v):.3f} (x{len(v)})"
        for k, v in sorted(by_label.items())), file=sys.stderr)
    for r in run.records:
        if "error" in r:
            print(f"failed {r['label']}: {r['error']}", file=sys.stderr)
    for name, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name} {c['value']!r} {rel} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
