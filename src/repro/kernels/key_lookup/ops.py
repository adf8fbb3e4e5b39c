"""Public wrapper for the join-key lookup kernel (registry-dispatched)."""
from __future__ import annotations

import jax
import numpy as np

from ...core.obs.trace import note_h2d
from ..registry import bucket, interpret_mode, padded, register, resolve
from .key_lookup import key_lookup_pallas
from .ref import key_lookup_ref


@jax.jit
def _key_lookup_jit(sorted_vals, probe):
    return key_lookup_pallas(sorted_vals, probe, interpret=interpret_mode())


@register("key_lookup", "pallas")
def _key_lookup_pallas(sorted_vals, probe) -> np.ndarray:
    n, g = len(probe), len(sorted_vals)
    if n == 0 or g == 0:
        return np.full(n, -1, dtype=np.int32)
    # NaN pads the dictionary: it never matches nor sorts below a probe
    table = padded(np.asarray(sorted_vals, np.float32), bucket(g), np.nan)
    probe = padded(probe, bucket(n))
    note_h2d(table, probe)
    codes = _key_lookup_jit(table, probe)
    return np.asarray(codes)[:n]


register("key_lookup", "ref", key_lookup_ref)


def key_lookup(sorted_vals, probe, engine: str = "auto"):
    """Map probe values to positions in a sorted dictionary (-1 = miss)."""
    return resolve("key_lookup", engine)(sorted_vals, probe)
