"""Public wrapper for the join-key lookup kernel (registry-dispatched)."""
from __future__ import annotations

import weakref

import jax
import numpy as np

from ...core.obs.trace import note_h2d, note_kernel_args
from ..registry import bucket, interpret_mode, padded, register, resolve
from .key_lookup import key_lookup_pallas, lookup_path
from .ref import key_lookup_ref


@jax.jit
def _key_lookup_jit(sorted_vals, probe):
    return key_lookup_pallas(sorted_vals, probe, interpret=interpret_mode())


class _DeviceDictionaries:
    """The padded device copy of each dictionary array a caller passes call
    after call (a join's build keys, probed once a morsel), made on its
    first call and dropped when the host array is: a later call moves only
    its probes.  Keyed by the host array's identity, which a weak reference
    keeps from being mistaken after the array is gone."""

    def __init__(self):
        self._copies = {}  # id(host array) -> (weakref to it, device copy)

    def get(self, sorted_vals: np.ndarray):
        key = id(sorted_vals)
        hit = self._copies.get(key)
        if hit is not None and hit[0]() is sorted_vals:
            return hit[1]
        # NaN pads the dictionary: it never matches nor sorts below a probe
        table = padded(sorted_vals.astype(np.float32, copy=False),
                       bucket(len(sorted_vals)), np.nan)
        note_h2d(table)
        copy = jax.device_put(table)
        try:
            ref = weakref.ref(sorted_vals,
                              lambda _r, k=key: self._copies.pop(k, None))
        except TypeError:  # not an object a weak reference can follow
            return copy
        self._copies[key] = (ref, copy)
        return copy


_DICTIONARIES = _DeviceDictionaries()


@register("key_lookup", "pallas")
def _key_lookup_pallas(sorted_vals, probe) -> np.ndarray:
    n, g = len(probe), len(sorted_vals)
    if n == 0 or g == 0:
        return np.full(n, -1, dtype=np.int32)
    table = _DICTIONARIES.get(np.asarray(sorted_vals))
    probe = padded(probe, bucket(n))
    note_h2d(probe)
    note_kernel_args(entries=g, path=lookup_path(table.shape[0]))
    codes = _key_lookup_jit(table, probe)
    return np.asarray(codes)[:n]


register("key_lookup", "ref", key_lookup_ref)


def key_lookup(sorted_vals, probe, engine: str = "auto"):
    """Map probe values to positions in a sorted dictionary (-1 = miss)."""
    return resolve("key_lookup", engine)(sorted_vals, probe)
