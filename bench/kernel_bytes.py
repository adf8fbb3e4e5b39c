"""Bytes at each relational kernel's interface, from the call's arguments.

A kernel's roofline time is the least time the chip needs to move these
bytes through HBM.  Each function counts what crosses the kernel's
interface, unpadded: the rows and columns in, and the mask, codes, lanes or
per-group results out, at the width the kernel's contract gives them
(float32 values, uint32 hashes and bitset words, int32 codes and lanes, one
byte per boolean).  It does not count what an implementation touches
beyond that, such as a lookup's compares against every dictionary entry, so
the same work reads the same whatever implements it.

All six kernels do O(1) arithmetic per byte moved, far below the chip's
ratio of operations to bandwidth, so bandwidth bounds each of them and the
roofline time is bytes over peak HBM bandwidth.

Each function takes the arguments the kernel registry's implementation is
called with, and returns bytes.
"""
from __future__ import annotations

F32 = U32 = I32 = 4
BOOL = 1


def filter_eval(columns, ops, lits) -> int:
    n = len(columns[0])
    return n * F32 * len(columns) + n * BOOL


def key_lookup(sorted_vals, probe) -> int:
    return len(sorted_vals) * F32 + len(probe) * (F32 + I32)


def bloom_probe(h1, h2, bits, num_hashes, num_bits) -> int:
    n = len(h1)
    return n * 2 * U32 + len(bits) * U32 + n * BOOL


def hash_group(codes, values, num_groups) -> int:
    return len(codes) * (I32 + F32) + num_groups * 2 * F32


hash_group_minmax = hash_group


def hash_partition(cols, num_partitions) -> int:
    n = len(cols[0])
    return n * F32 * len(cols) + n * I32


BYTES = {
    "filter_eval": filter_eval,
    "key_lookup": key_lookup,
    "bloom_probe": bloom_probe,
    "hash_group": hash_group,
    "hash_group_minmax": hash_group_minmax,
    "hash_partition": hash_partition,
}
