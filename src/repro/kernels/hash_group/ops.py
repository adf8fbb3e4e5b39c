"""Public wrapper for the grouped-aggregation kernel (registry-dispatched)."""
from __future__ import annotations

import functools

import jax
import numpy as np

from ...core.obs.trace import note_h2d
from ..registry import (GROUP_BUCKET_FLOOR, bucket, interpret_mode, padded,
                        register, resolve)
from .hash_group import hash_group_minmax_pallas, hash_group_pallas
from .ref import hash_group_minmax_ref, hash_group_ref


@functools.partial(jax.jit, static_argnames=("num_groups",))
def _hash_group_jit(codes, values, num_groups: int):
    return hash_group_pallas(codes, values, num_groups,
                             interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("num_groups",))
def _hash_group_minmax_jit(codes, values, num_groups: int):
    return hash_group_minmax_pallas(codes, values, num_groups,
                                    interpret=interpret_mode())


def _bucketed(jitted, codes, values, num_groups: int):
    """Pad rows (code -1: no group) and the group domain to their buckets,
    then cut the per-group results back to ``num_groups``."""
    rows = bucket(len(codes))
    codes, values = padded(codes, rows, -1), padded(values, rows)
    note_h2d(codes, values)
    out = jitted(codes, values, bucket(num_groups, GROUP_BUCKET_FLOOR))
    return tuple(np.asarray(a)[:num_groups] for a in out)


@register("hash_group", "pallas")
def _hash_group_pallas(codes, values, num_groups: int):
    return _bucketed(_hash_group_jit, codes, values, num_groups)


register("hash_group", "ref", hash_group_ref)


@register("hash_group_minmax", "pallas")
def _hash_group_minmax_pallas(codes, values, num_groups: int):
    return _bucketed(_hash_group_minmax_jit, codes, values, num_groups)


register("hash_group_minmax", "ref", hash_group_minmax_ref)


def hash_group(codes, values, num_groups: int, engine: str = "auto"):
    return resolve("hash_group", engine)(codes, values, num_groups)


def hash_group_minmax(codes, values, num_groups: int, engine: str = "auto"):
    return resolve("hash_group_minmax", engine)(codes, values, num_groups)
