"""repro.kernels: the relational kernels' share of their roofline, in %.

The least time the chip needs for the kernel calls made while the profiler
traced (their interface bytes, ``bench/kernel_bytes.py``, over the chip's
peak HBM bandwidth: every one of them is bound by bandwidth), over the
device time of those kernels' programs in the trace."""


def read(run):
    if not run.profile or not run.peaks:
        return None
    device_s = sum(run.profile.get("kernel_s", {}).values())
    moved = sum(run.kernel_bytes.values())
    if device_s <= 0 or moved <= 0:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / device_s
