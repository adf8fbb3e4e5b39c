"""repro.kernels key_lookup: the share of the traced window the chip spent
in the join-key lookup's program (``_key_lookup_jit``), in %.  Nothing to
read without a trace."""


def read(run):
    p = run.profile
    if not p or p.get("window_s", 0) <= 0 or "kernel_s" not in p:
        return None
    return 100.0 * p["kernel_s"].get("key_lookup", 0.0) / p["window_s"]
