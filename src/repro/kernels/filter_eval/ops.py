"""Public wrapper for the vectorized filter kernel (registry-dispatched)."""
from __future__ import annotations

import functools

import jax
import numpy as np

from ...core.obs.trace import note_h2d
from ..registry import bucket, interpret_mode, padded, register, resolve
from .filter_eval import filter_eval_pallas
from .ref import filter_eval_ref


@functools.partial(jax.jit, static_argnames=("ops", "lits"))
def _filter_eval_jit(columns, ops: tuple, lits: tuple):
    return filter_eval_pallas(list(columns), ops, lits,
                              interpret=interpret_mode())


@register("filter_eval", "pallas")
def _filter_eval_pallas(columns, ops: tuple, lits: tuple) -> np.ndarray:
    n = len(columns[0])
    rows = bucket(n)
    columns = tuple(padded(c, rows) for c in columns)
    note_h2d(*columns)
    mask = _filter_eval_jit(columns, ops, lits)
    return np.asarray(mask)[:n]


register("filter_eval", "ref", filter_eval_ref)


def filter_eval(columns, ops: tuple, lits: tuple, engine: str = "auto"):
    return resolve("filter_eval", engine)(columns, ops, lits)
