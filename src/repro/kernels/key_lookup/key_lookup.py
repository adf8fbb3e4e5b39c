"""Join-key dictionary lookup (factorized hash-join probe) for the TPU.

The streaming hash join dictionary-encodes the build side's key columns once
(sorted uniques); every probe morsel then maps its key values into build
codes: per probe value, the number of dictionary entries strictly below it
(the leftmost insertion point, as ``searchsorted``) and whether the entry
there equals it.  Two programs compute it, chosen by the dictionary's static
size:

* **compare-and-reduce** (a Pallas kernel), up to
  :data:`COMPARE_MAX_ENTRIES` entries: the TPU has no gather unit for a
  per-row dictionary load, so the sorted dictionary sits in VMEM as one
  ``(rows, 128)`` tile and each probe block walks all of its rows, comparing
  every probe value against 128 entries per step on the VPU.  Its cost grows
  as probes x entries.
* **blocked search**, above that: the dictionary stays in HBM as rows of 128
  entries, and a tree of fences (the first entry of every row, then of
  every row of fences) narrows each probe to one row per level.  A level is
  one gather of a 128-entry row per probe and one 128-wide compare, so the
  cost grows as probes x log128(entries); 2^21 entries take three levels.

The dictionary is padded with NaN, which compares false both ways, so
padding neither shifts an insertion point nor produces a match.

Returns, per probe value, the dictionary position of an exact match or -1 —
i.e. ``searchsorted`` + equality in one program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 1024
LANES = 128
# the largest (padded) dictionary the compare-and-reduce kernel takes; above
# it the blocked search runs.  It belongs at the crossover of the two
# programs' device times on a TPU v5e, which
# benchmarks/key_lookup_crossover.py measures; 8192 is the predicted one,
# not yet measured (PERF.md).
COMPARE_MAX_ENTRIES = 8192


def lookup_path(entries: int) -> str:
    """The program a dictionary of ``entries`` (padded) entries takes."""
    return "compare" if entries <= COMPARE_MAX_ENTRIES else "blocked"


def _lookup_kernel(dict_ref, probe_ref, out_ref):
    probe = probe_ref[...][:, None]  # (B, 1) float32

    def step(r, carry):
        below, equal = carry
        entries = dict_ref[pl.ds(r, 1), :]  # (1, 128) float32, NaN-padded
        below = below + (entries < probe).astype(jnp.int32)
        equal = jnp.maximum(equal, (entries == probe).astype(jnp.int32))
        return below, equal

    zeros = jnp.zeros((probe.shape[0], LANES), jnp.int32)
    below, equal = jax.lax.fori_loop(0, dict_ref.shape[0], step,
                                     (zeros, zeros))
    out_ref[...] = jnp.where(jnp.max(equal, axis=1) > 0,
                             jnp.sum(below, axis=1), -1)


def _dictionary_tile(sorted_vals):
    """(G,) dictionary -> NaN-padded (rows, 128) float32 tile, rows % 8 == 0."""
    g = sorted_vals.shape[0]
    rows = max(8, -(-g // (8 * LANES)) * 8)
    return jnp.pad(sorted_vals.astype(jnp.float32), (0, rows * LANES - g),
                   constant_values=jnp.nan).reshape(rows, LANES)


def _rank(entries, probe):
    """``(below, equal)`` per probe: how many of the sorted, NaN-padded
    ``entries`` are below it, and whether the entry at that position equals
    it.  Each level gathers one 128-entry row per probe; the row is found
    from the rank of the probe among the rows' first entries (the fences),
    ranked the same way one level up."""
    p = probe[:, None]
    rows = -(-entries.shape[0] // LANES)
    table = jnp.pad(entries, (0, rows * LANES - entries.shape[0]),
                    constant_values=jnp.nan).reshape(rows, LANES)
    if rows == 1:
        row = table  # (1, 128): every probe's row
        top = None
    else:
        fences, top = _rank(table[:, 0], probe)
        row = jnp.take(table, jnp.maximum(fences - 1, 0), axis=0)
    below = jnp.sum((row < p).astype(jnp.int32), axis=1)
    equal = jnp.any(row == p, axis=1)
    if top is None:
        return below, equal
    first = jnp.maximum(fences - 1, 0) * LANES
    # all of the row is below: the entry at the rank is the next fence
    return first + below, equal | ((below == LANES) & top)


def _blocked_lookup(sorted_vals, probe):
    below, equal = _rank(sorted_vals.astype(jnp.float32),
                         probe.astype(jnp.float32))
    return jnp.where(equal, below, -1).astype(jnp.int32)


def key_lookup_pallas(sorted_vals, probe, interpret: bool = True):
    """sorted_vals: (G,) float32 ascending (no NaN); probe: (N,) float32.

    Returns (N,) int32: index of the exact match in ``sorted_vals``, or -1.
    A dictionary of more than :data:`COMPARE_MAX_ENTRIES` entries takes the
    blocked search, which needs no Pallas kernel (``interpret`` unused).
    """
    g = sorted_vals.shape[0]
    n = probe.shape[0]
    if g == 0 or n == 0:
        return jnp.full((n,), -1, dtype=jnp.int32)
    if lookup_path(g) == "blocked":
        return _blocked_lookup(sorted_vals, probe)
    return _compare_lookup(sorted_vals, probe, interpret)


def _compare_lookup(sorted_vals, probe, interpret: bool):
    """The compare-and-reduce kernel over the whole dictionary."""
    n = probe.shape[0]
    table = _dictionary_tile(sorted_vals)
    block = min(ROW_BLOCK, max(((n + 7) // 8) * 8, 8))
    pad = (-n) % block
    probe_p = jnp.pad(probe.astype(jnp.float32), (0, pad))
    out = pl.pallas_call(
        _lookup_kernel,
        grid=((n + pad) // block,),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (0, 0)),  # whole dictionary
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n + pad,), jnp.int32),
        interpret=interpret,
    )(table, probe_p)
    return out[:n]
