"""Public wrapper + host-side bridge for the bloom-probe kernel."""
from __future__ import annotations

import functools

import jax
import numpy as np

from ...core.bloomfilter import BloomFilter, hash_values
from ...core.obs.trace import note_h2d
from ..registry import bucket, interpret_mode, padded, register, resolve
from .bloom import bloom_probe_pallas
from .ref import bloom_probe_ref


@functools.partial(jax.jit, static_argnames=("num_hashes", "num_bits"))
def _bloom_probe_jit(h1, h2, bits, num_hashes: int, num_bits: int):
    return bloom_probe_pallas(h1, h2, bits, num_hashes, num_bits,
                              interpret=interpret_mode())


@register("bloom_probe", "pallas")
def _bloom_probe_pallas(h1, h2, bits, num_hashes: int,
                        num_bits: int) -> np.ndarray:
    n = len(h1)
    rows = bucket(n)
    h1, h2 = padded(h1, rows), padded(h2, rows)
    note_h2d(h1, h2, bits)  # the bit array goes to the device every call
    hits = _bloom_probe_jit(h1, h2, bits, num_hashes, num_bits)
    return np.asarray(hits)[:n]


register("bloom_probe", "ref", bloom_probe_ref)


def bloom_probe(h1, h2, bits, num_hashes: int, num_bits: int,
                engine: str = "auto"):
    return resolve("bloom_probe", engine)(h1, h2, bits, num_hashes, num_bits)


def bloom_operands(bf: BloomFilter, values: np.ndarray) -> tuple:
    """The ``bloom_probe`` arguments that probe ``bf`` for ``values``: the
    two 32-bit halves of each value's hash, hashed on the host, then the
    filter's bit array as 32-bit words and its shape."""
    h = hash_values(values)
    h1 = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    h2 = (h >> np.uint64(32)).astype(np.uint32)
    return h1, h2, bf.bits.view(np.uint32), bf.num_hashes, bf.num_bits


def probe_bloom_filter(bf: BloomFilter, values: np.ndarray,
                       engine: str = "auto") -> np.ndarray:
    """Probe a core.bloomfilter.BloomFilter via the TPU kernel path."""
    return np.asarray(bloom_probe(*bloom_operands(bf, values), engine=engine))
