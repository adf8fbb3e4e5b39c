"""Device time of the join-key lookup's two programs by dictionary size: the
compare-and-reduce kernel and the blocked search, each at every padded
dictionary size from 2^10 to 2^21 entries (the compare kernel only while
its walk stays short), probing a stripe (8192 rows) and a morsel (1024).
The crossover sets ``COMPARE_MAX_ENTRIES`` in
``repro/kernels/key_lookup/key_lookup.py``.

    python benchmarks/key_lookup_crossover.py [--reps 50] [--out FILE]

Run it on a TPU: it times compiled programs, and on the CPU it would time
the interpreter.  Each timing is the median of ``--reps`` calls of one
jitted program, each waited for on the device; one JSON line a
measurement, and a last line naming the largest size at which the compare
kernel is the faster at both probe sizes.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.key_lookup.key_lookup import (  # noqa: E402
    _blocked_lookup, _compare_lookup)
from repro.kernels.registry import interpret_mode  # noqa: E402

COMPARE_LARGEST = 1 << 17  # past this the compare kernel's walk is long


def _time(fn, *args, reps):
    fn(*args).block_until_ready()  # compile
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    interpret = interpret_mode()
    programs = {
        "compare": jax.jit(lambda d, p: _compare_lookup(d, p, interpret)),
        "blocked": jax.jit(_blocked_lookup),
    }
    rng = np.random.default_rng(0)
    rows, faster = [], {}
    for g in [1 << k for k in range(10, 22)]:
        table = jnp.asarray(np.arange(g, dtype=np.float32) * 4)
        for n in (1024, 8192):
            probe = jnp.asarray(rng.integers(0, 4 * g, n).astype(np.float32))
            want = None
            for path, fn in programs.items():
                if path == "compare" and g > COMPARE_LARGEST:
                    continue
                got = np.asarray(fn(table, probe))
                want = got if want is None else want
                assert np.array_equal(got, want), (g, n, path)
                s = _time(fn, table, probe, reps=args.reps)
                rec = {"entries": g, "probes": n, "path": path,
                       "seconds": s, "device": jax.devices()[0].device_kind}
                rows.append(rec)
                print(json.dumps(rec), flush=True)
            times = {r["path"]: r["seconds"] for r in rows
                     if r["entries"] == g and r["probes"] == n}
            faster.setdefault(g, []).append(
                times.get("compare", float("inf")) <= times["blocked"])
    best = max((g for g, f in faster.items() if all(f)), default=None)
    print(json.dumps({"compare_faster_up_to": best}), flush=True)
    if args.out:
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
