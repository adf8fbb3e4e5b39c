"""The device's idle time under the program's own spans.

The warehouse mirrors each live span of a traced query into the profiler's
trace as a host annotation of the same name (``repro.core.obs.trace``):
``kernel.<k>`` for a kernel round trip, ``llap.read`` for a chunk read that
missed the LLAP cache, ``scan.io_wait`` for a scan waiting on its reads,
``stage:<s>``, ``wlm:...``, ``sched:...`` and ``fed:...``.  This module
reads the same planes as :func:`profiling.reduce_planes` (from
:func:`profiling.read_xplane`) and gives, for the traced window, the time
the device idled while some host thread was inside each span name.

A span name's time is the union of its intervals: a thread's spans are
merged, then the threads' unions are merged, so two threads in
``kernel.key_lookup`` at once count that idle time once.
"""
from __future__ import annotations

from .profiling import WINDOW, _merged

KERNEL = "kernel."
# the program's span names start with one of these; the benchmark's own
# annotations start with "bench." and XLA's host events with neither
PROGRAM_PREFIXES = (KERNEL, "llap.", "scan.", "stage:", "wlm:", "sched:",
                    "fed:")


def _overlap(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_spans(planes, top: int = 10) -> dict:
    """Idle seconds of the device under each program span name.

    Returns ``{}`` without a ``bench.window`` annotation or a device op in
    it; else ``idle_s`` (the window less the union of the device ops),
    ``spans`` (the ``top`` names by idle seconds under them, as ``[name,
    seconds]``), ``kernel_calls_s`` (idle seconds under any ``kernel.*``
    span) and ``uncovered_s`` (idle seconds under no program span).
    """
    host, device = [], []
    for pname, lines in planes:
        if pname.startswith("/device:") and not pname.startswith(
                "/device:CUSTOM"):
            by_line = dict(lines)
            ops = by_line.get("XLA Ops")
            if ops is None:  # as reduce_planes: every event of the plane
                ops = [ev for evs in by_line.values() for ev in evs]
            device.extend((s, e) for _n, s, e in ops)
        elif pname.startswith("/host:"):
            host.extend(ev for _ln, evs in lines for ev in evs)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        return {}
    w0, w1 = windows[0]
    busy = _merged([(max(s, w0), min(e, w1)) for s, e in device
                    if e > w0 and s < w1])
    if not busy:
        return {}
    idle, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)

    by_name = {}
    for n, s, e in host:
        if n.startswith(PROGRAM_PREFIXES) and e > w0 and s < w1:
            by_name.setdefault(n, []).append((max(s, w0), min(e, w1)))
    under = {n: _overlap(idle, _merged(ivs)) / 1e9
             for n, ivs in by_name.items()}
    kernels = _merged([iv for n, ivs in by_name.items()
                       if n.startswith(KERNEL) for iv in ivs])
    covered = _merged([iv for ivs in by_name.values() for iv in ivs])
    idle_ns = sum(e - s for s, e in idle)
    ranked = sorted(under.items(), key=lambda kv: (-kv[1], kv[0]))
    return {
        "idle_s": idle_ns / 1e9,
        "spans": [[n, t] for n, t in ranked[:top] if t > 0],
        "kernel_calls_s": _overlap(idle, kernels) / 1e9,
        "uncovered_s": (idle_ns - _overlap(idle, covered)) / 1e9,
    }
