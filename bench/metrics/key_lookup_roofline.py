"""repro.kernels key_lookup: the join-key lookup's share of its roofline,
in %.

The least time the chip needs for the ``key_lookup`` calls made while the
profiler traced (their interface bytes, ``bench/kernel_bytes.py``, over the
chip's peak HBM bandwidth), over the device time of the lookup's program
(``_key_lookup_jit``) in the trace.  Nothing to read without a traced
lookup."""


def read(run):
    if not run.profile or not run.peaks:
        return None
    device_s = run.profile.get("kernel_s", {}).get("key_lookup", 0.0)
    moved = run.kernel_bytes.get("key_lookup", 0)
    if device_s <= 0 or moved <= 0:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / device_s
