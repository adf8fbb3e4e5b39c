"""Compile counting, kernel-call capture and the profiler-trace reduction.

The reduction reads one ``.xplane.pb`` that ``jax.profiler`` wrote for the
traced part of a window and returns what the per-layer metrics and the
``breakdown`` need:

* the traced window: the benchmark's own ``bench.window`` annotation;
* device busy time: the union of the intervals of the device's XLA ops,
  clipped to the window, averaged over the device planes;
* device time per kernel: the XLA module events whose name holds a kernel's
  jitted program name (``KERNEL_PROGRAMS``);
* the device ops that took most time;
* the longest idle gaps of the device, each named by what the host was
  doing in it: inside a kernel call (its ``bench.kernel:<k>`` annotation),
  or between kernel calls of the queries in flight, from the run's own
  record of when each query was sent and answered (a ``bench.query:<q>``
  annotation that began before the trace is not in it).
"""
from __future__ import annotations

import glob
import threading
from collections import defaultdict

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# kernel -> the name of the jitted program that runs it (the program's own
# names, kernels/*/ops.py); the trace's XLA module events carry them
KERNEL_PROGRAMS = {
    "filter_eval": "_filter_eval_jit",
    "key_lookup": "_key_lookup_jit",
    "bloom_probe": "_bloom_probe_jit",
    "hash_group": "_hash_group_jit",
    "hash_group_minmax": "_hash_group_minmax_jit",
    "hash_partition": "_hash_partition_jit",
}

WINDOW = "bench.window"
QUERY = "bench.query:"
KERNEL = "bench.kernel:"


class CompileCounter:
    """Counts XLA backend compilations, and persistent-cache hits, through
    ``jax.monitoring`` listeners; the warehouse compiles from several
    threads at once."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1


class KernelCalls:
    """Wraps each registered Pallas kernel so that, while ``active``, every
    call records its interface bytes and runs inside a
    ``bench.kernel:<name>`` profiler annotation."""

    def __init__(self, jax, registry, byte_fns: dict):
        self.active = False
        self.bytes = defaultdict(int)
        self._lock = threading.Lock()
        annotate = jax.profiler.TraceAnnotation
        for name, nbytes in byte_fns.items():
            registry.register(name, "pallas", self._wrap(
                name, registry.resolve(name, "pallas"), nbytes, annotate))

    def _wrap(self, name, fn, nbytes, annotate):
        label = KERNEL + name

        def call(*args):
            if not self.active:
                return fn(*args)
            with annotate(label):
                out = fn(*args)
            with self._lock:
                self.bytes[name] += nbytes(*args)
            return out

        return call


def _merged(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_planes(planes, queries=()) -> dict:
    """Reduce profiler planes to window, busy, per-kernel and gap numbers.

    ``planes`` is a sequence of ``(plane name, [(line name, [(event name,
    start_ns, end_ns), ...]), ...])``; :func:`read_xplane` gives it from a
    file.  ``queries`` holds ``(label, start_s, end_s)`` of each query, in
    seconds from the window's start.  Times are in seconds in the result.
    """
    host, devices = [], []
    for pname, lines in planes:
        if pname.startswith("/device:") and not pname.startswith(
                "/device:CUSTOM"):
            devices.append(lines)
        elif pname.startswith("/host:"):
            host.extend(ev for _ln, evs in lines for ev in evs)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        return {}
    w0, w1 = windows[0]
    window = w1 - w0

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                if e > w0 and s < w1]

    busy, op_time, kernel_time = [], defaultdict(int), defaultdict(int)
    device_ivs = []
    for lines in devices:
        by_line = {ln: clip(evs) for ln, evs in lines}
        ops = by_line.get("XLA Ops")
        if ops is None:  # no op line: fall back to every event the plane has
            ops = [ev for evs in by_line.values() for ev in evs]
        if not ops:
            continue
        ivs = [(s, e) for _n, s, e in ops]
        busy.append(sum(e - s for s, e in _merged(ivs)))
        device_ivs.extend(ivs)
        for n, s, e in ops:
            op_time[_short(n)] += e - s
        for n, s, e in by_line.get("XLA Modules", []):
            for k, prog in KERNEL_PROGRAMS.items():
                if prog in n:
                    kernel_time[k] += e - s
    if not busy:
        return {"window_s": window / 1e9}

    queries = clip([(label, w0 + int(a * 1e9), w0 + int(b * 1e9))
                    for label, a, b in queries])
    kernels = [(n[len(KERNEL):], s, e) for n, s, e in clip(host)
               if n.startswith(KERNEL)]
    gaps = []
    prev = w0
    for s, e in _merged(device_ivs) + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_activity(a, b, queries, kernels), (b - a) / 1e9]
            for a, b in gaps[:10]]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_time.items()},
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": idle,
    }


def _short(op: str) -> str:
    """An XLA op event's name without its layouts and operands:
    ``_bloom_probe_jit.1 = s32[8192]`` from the instruction's text."""
    return op.split("{", 1)[0].split("(", 1)[0].lstrip("%").strip()


def _host_activity(a, b, queries, kernels) -> str:
    """What the host was doing while the device idled over ``[a, b]``."""
    inside = defaultdict(int)
    for k, s, e in kernels:
        inside[k] += max(0, min(b, e) - max(a, s))
    if inside:
        k, t = max(inside.items(), key=lambda kv: kv[1])
        if 2 * t >= b - a:
            return f"host side of a {k} call"
    live = sorted({q for q, s, e in queries if s < b and e > a})
    if not live:
        return "no query in flight"
    names = ",".join(live[:4]) + (f",+{len(live) - 4}" if len(live) > 4
                                  else "")
    return f"host between kernel calls: {names}"


def read_xplane(path: str):
    """``(plane name, [(line name, [(event name, start_ns, end_ns)])])`` for
    every plane of one profiler output file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                                 for ev in ln.events])
                      for ln in p.lines])
            for p in data.planes]


def find_xplane(log_dir: str) -> str:
    found = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one profiler trace under {log_dir}, "
                           f"found {len(found)}")
    return found[0]
