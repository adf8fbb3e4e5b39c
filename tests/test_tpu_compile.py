"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode (``tests/test_kernels.py``) cannot show what the chip's
compiler refuses: unsupported gathers, unaligned slices, too much VMEM.
These tests compile every kernel the SQL path dispatches with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology, at the
widths the executor feeds them: a morsel (1024 rows), a stripe tail (1000),
a stripe or shuffle lane batch (8192), group domains of 128 and 1024,
every join dictionary bucket from 2^10 to the 2^21 entries of TPC-H's 1.5M
order keys (the compare-and-reduce kernel up to its threshold, the blocked
search above it), and bloom bitsets up to the 2^25 bits a build of that
size can make.  Nothing executes: a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler's library at a time, so describing
it while test modules are collected would break parallel test workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bloom.bloom import bloom_probe_pallas
from repro.kernels.filter_eval.filter_eval import filter_eval_pallas
from repro.kernels.hash_group.hash_group import (hash_group_minmax_pallas,
                                                 hash_group_pallas)
from repro.kernels.hash_partition.hash_partition import hash_partition_pallas
from repro.kernels.key_lookup.key_lookup import key_lookup_pallas, lookup_path

ROWS = [1000, 1024, 8192]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _compile_for_chip(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"


def _arg(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("n", ROWS)
def test_filter_eval_compiles(one_chip, n):
    col = _arg(one_chip, (n,), jnp.float32)
    _compile_for_chip(
        lambda a, b: filter_eval_pallas([a, b], (0, 3), (25.0, 1200.0),
                                        interpret=False), col, col)


@pytest.mark.parametrize("n", ROWS)
def test_hash_partition_compiles(one_chip, n):
    col = _arg(one_chip, (n,), jnp.float32)
    _compile_for_chip(
        lambda a, b: hash_partition_pallas((a, b), 4, interpret=False),
        col, col)


@pytest.mark.parametrize("n,g", [(1024, 128), (1000, 1024), (8192, 1024)])
@pytest.mark.parametrize("kernel", [hash_group_pallas,
                                    hash_group_minmax_pallas])
def test_hash_group_compiles(one_chip, kernel, n, g):
    _compile_for_chip(
        lambda c, v: kernel(c, v, g, interpret=False),
        _arg(one_chip, (n,), jnp.int32), _arg(one_chip, (n,), jnp.float32))


@pytest.mark.parametrize("n,g", [(1000, 1024), (1024, 4096), (8192, 4096),
                                 (8192, 128)])
def test_key_lookup_compiles(one_chip, n, g):
    _compile_for_chip(
        lambda d, p: key_lookup_pallas(d, p, interpret=False),
        _arg(one_chip, (g,), jnp.float32), _arg(one_chip, (n,), jnp.float32))


# every dictionary bucket a join can build, to the 2^21 that TPC-H's 1.5M
# order keys take: the compare-and-reduce kernel up to its threshold, the
# blocked search above it
DICT_BUCKETS = [1 << k for k in range(10, 22)]


@pytest.mark.parametrize("g", DICT_BUCKETS)
@pytest.mark.parametrize("n", [1024, 8192])
def test_key_lookup_compiles_at_every_bucket(one_chip, n, g):
    compiled = jax.jit(
        lambda d, p: key_lookup_pallas(d, p, interpret=False)).lower(
            _arg(one_chip, (g,), jnp.float32),
            _arg(one_chip, (n,), jnp.float32)).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    assert mosaic == (lookup_path(g) == "compare")


# the bloom filters a build of up to 1.5M keys can make: 2^24 and 2^25 bits
@pytest.mark.parametrize("words", [1 << 19, 1 << 20])
def test_bloom_probe_compiles_at_the_largest_filters(one_chip, words):
    h = _arg(one_chip, (8192,), jnp.uint32)
    _compile_for_chip(
        lambda a, b, bits: bloom_probe_pallas(a, b, bits, 6, words * 32,
                                              interpret=False),
        h, h, _arg(one_chip, (words,), jnp.uint32))


@pytest.mark.parametrize("n,words,hashes", [(1000, 2, 1), (1024, 1024, 6),
                                            (8192, 1024, 6),
                                            (8192, 4096, 8)])
def test_bloom_probe_compiles(one_chip, n, words, hashes):
    h = _arg(one_chip, (n,), jnp.uint32)
    _compile_for_chip(
        lambda a, b, bits: bloom_probe_pallas(a, b, bits, hashes, words * 32,
                                              interpret=False),
        h, h, _arg(one_chip, (words,), jnp.uint32))
