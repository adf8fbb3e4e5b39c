"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.bloom.ops import probe_bloom_filter
from repro.kernels.filter_eval.ops import filter_eval
from repro.kernels.filter_eval.ref import filter_eval_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hash_group.ops import hash_group
from repro.kernels.hash_group.ref import hash_group_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref


@pytest.mark.parametrize("n,ncols", [(100, 1), (1024, 2), (5000, 3), (8192, 2)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int64])
def test_filter_eval_sweep(n, ncols, dtype):
    rng = np.random.default_rng(n)
    cols = [jnp.asarray(rng.uniform(0, 100, n).astype(dtype)) for _ in range(ncols)]
    ops = tuple((i % 6) for i in range(ncols))
    lits = tuple(float(rng.uniform(20, 80)) for _ in range(ncols))
    got = filter_eval(cols, ops, lits)
    exp = filter_eval_ref(cols, ops, lits)
    assert (np.array(got) == np.array(exp)).all()


@pytest.mark.parametrize("n,g", [(100, 5), (4096, 128), (10_000, 37), (2048, 1000)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_hash_group_sweep(n, g, dtype):
    rng = np.random.default_rng(g)
    codes = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(dtype))
    s1, c1 = hash_group(codes, vals, g)
    s2, c2 = hash_group_ref(codes, vals, g)
    np.testing.assert_allclose(np.array(s1), np.array(s2), atol=1e-3)
    np.testing.assert_array_equal(np.array(c1), np.array(c2))


@pytest.mark.parametrize("shape", [(1, 1, 64, 16), (2, 3, 128, 32),
                                   (2, 2, 256, 64), (1, 4, 96, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, dtype):
    B, H, S, d = shape
    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.normal(size=shape), dtype)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    bq = 32 if S % 32 == 0 else S
    got = flash_attention(q, k, v, block_q=bq, block_k=bq)
    exp = attention_ref(q, k, v)
    atol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(exp, np.float32), atol=atol)


@pytest.mark.parametrize("cfg", [(1, 32, 2, 8, 4, 8), (2, 64, 3, 16, 8, 16),
                                 (1, 128, 4, 32, 16, 32), (2, 96, 2, 8, 8, 48)])
def test_ssd_scan_sweep(cfg):
    B, S, H, P, N, Q = cfg
    rng = np.random.default_rng(S)
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32)) * 0.1
    dA = -jnp.abs(jnp.asarray(rng.normal(size=(B, S, H)).astype(np.float32))) * 0.2
    Bm = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32)) * 0.3
    Cm = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32)) * 0.3
    got, _ = ssd_scan(x, dA, Bm, Cm, chunk=Q)
    exp = ssd_scan_ref(x, dA, Bm, Cm, chunk=Q)
    np.testing.assert_allclose(np.array(got), np.array(exp), atol=5e-5, rtol=1e-3)


def test_ssd_scan_chunk_invariance():
    """Same result regardless of chunking — the invariant behind SSD."""
    rng = np.random.default_rng(0)
    B, S, H, P, N = 1, 64, 2, 8, 4
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32)) * 0.1
    dA = -jnp.abs(jnp.asarray(rng.normal(size=(B, S, H)).astype(np.float32))) * 0.2
    Bm = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32)) * 0.3
    Cm = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32)) * 0.3
    y8, _ = ssd_scan(x, dA, Bm, Cm, chunk=8)
    y32, _ = ssd_scan(x, dA, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.array(y8), np.array(y32), atol=5e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 2000), k=st.integers(1, 500))
def test_property_bloom_kernel_matches_host(n, k):
    from repro.core.bloomfilter import BloomFilter

    rng = np.random.default_rng(n * 1000 + k)
    members = rng.integers(0, 1_000_000, k)
    bf = BloomFilter.for_expected(k)
    bf.add(members)
    queries = np.concatenate([members, rng.integers(0, 1_000_000, n)])
    got = probe_bloom_filter(bf, queries)
    exp = bf.might_contain(queries)
    assert (got == exp).all()


# ---- join-key lookup and bloom probe: compare-and-reduce kernels vs ref ----
def _key_lookup_case(g, n, seed):
    """Sorted dictionary with duplicates, and probes with misses."""
    rng = np.random.default_rng(seed)
    sorted_vals = np.sort(rng.integers(0, max(2 * g, 1), g)).astype(np.float32)
    probe = rng.integers(-5, 2 * g + 5, n).astype(np.float32)
    return sorted_vals, probe


@pytest.mark.parametrize("g,n", [(1, 7), (9, 1000), (128, 1024), (129, 3000),
                                 (1024, 1024), (1025, 2048), (2556, 5000)])
def test_key_lookup_pallas_matches_ref(g, n):
    from repro.kernels.key_lookup.ops import key_lookup
    from repro.kernels.key_lookup.ref import key_lookup_ref

    sorted_vals, probe = _key_lookup_case(g, n, seed=g * 31 + n)
    got = np.asarray(key_lookup(sorted_vals, probe, engine="pallas"))
    exp = np.asarray(key_lookup_ref(jnp.asarray(sorted_vals),
                                    jnp.asarray(probe)))
    np.testing.assert_array_equal(got, exp)
    assert (got == -1).any() and (got >= 0).any()


def test_key_lookup_duplicates_at_block_edges():
    """Runs of equal entries that straddle 128-lane rows and the 1024-entry
    tile resolve to the run's leftmost position, as searchsorted does."""
    from repro.kernels.key_lookup.ops import key_lookup
    from repro.kernels.key_lookup.ref import key_lookup_ref

    sorted_vals = np.repeat(np.arange(400, dtype=np.float32), 4)[:1300]
    sorted_vals[120:136] = sorted_vals[120]   # across the first lane row
    sorted_vals[1016:1032] = sorted_vals[1016]  # across the tile edge
    probe = np.arange(-2, 330, 0.5, dtype=np.float32)
    got = np.asarray(key_lookup(sorted_vals, probe, engine="pallas"))
    exp = np.asarray(key_lookup_ref(jnp.asarray(sorted_vals),
                                    jnp.asarray(probe)))
    np.testing.assert_array_equal(got, exp)
    assert got[probe == sorted_vals[120]][0] == 120
    assert got[probe == sorted_vals[1016]][0] == 1016


def _threshold_sizes():
    from repro.kernels.key_lookup.key_lookup import COMPARE_MAX_ENTRIES

    t = COMPARE_MAX_ENTRIES
    # one entry under the threshold, at it (both compare-and-reduce), one
    # over it and several 1024-entry tiles over it (the blocked search)
    return [t - 1, t, t + 1, 5 * t + 3 * 1024 + 17]


@pytest.mark.parametrize("g", _threshold_sizes())
def test_key_lookup_pallas_matches_ref_around_the_threshold(g):
    """Misses, repeated probes, runs of equal entries across row and tile
    edges, and both ends of the dictionary, on each side of the size where
    the lookup changes program."""
    from repro.kernels.key_lookup.key_lookup import lookup_path
    from repro.kernels.key_lookup.ops import key_lookup
    from repro.kernels.key_lookup.ref import key_lookup_ref
    from repro.kernels.registry import bucket

    rng = np.random.default_rng(g)
    sorted_vals = np.sort(rng.choice(4 * g, g, replace=False)).astype(
        np.float32)
    sorted_vals[120:136] = sorted_vals[120]  # a run across a lane row
    sorted_vals[1016:1040] = sorted_vals[1016]  # and across a tile edge
    ends = sorted_vals[[0, 1, -2, -1]]
    probe = np.concatenate([
        ends, ends - 1, ends + 1, sorted_vals[[120, 1016]],
        rng.choice(sorted_vals, 900),  # hits, many repeated
        rng.integers(-3, 4 * g + 3, 1100).astype(np.float32)])  # misses too
    got = np.asarray(key_lookup(sorted_vals, probe, engine="pallas"))
    exp = np.asarray(key_lookup_ref(jnp.asarray(sorted_vals),
                                    jnp.asarray(probe)))
    np.testing.assert_array_equal(got, exp)
    assert got[4] == -1 and got[:4].tolist() == [0, 1, g - 2, g - 1]
    assert got[12] == 120 and got[13] == 1016
    assert (got == -1).any() and len(np.unique(got[14:914])) < 900
    want_path = "compare" if g <= _threshold_sizes()[1] else "blocked"
    assert lookup_path(bucket(g)) == want_path


@pytest.mark.parametrize("engine", ["pallas", "ref"])
def test_key_lookup_empty_dictionary(engine):
    from repro.kernels.key_lookup.ops import key_lookup

    got = np.asarray(key_lookup(np.zeros(0, np.float32),
                                np.arange(5, dtype=np.float32), engine=engine))
    np.testing.assert_array_equal(got, np.full(5, -1))


def test_key_lookup_non_finite_probes_miss():
    from repro.kernels.key_lookup.ops import key_lookup

    probe = np.array([np.nan, np.inf, -np.inf, 3.0], np.float32)
    got = np.asarray(key_lookup(np.arange(10, dtype=np.float32), probe,
                                engine="pallas"))
    np.testing.assert_array_equal(got, [-1, -1, -1, 3])


@pytest.mark.parametrize("members,n", [(1, 100), (2556, 3000),
                                       (50_000, 2048)])
def test_bloom_probe_pallas_matches_ref_across_tiles(members, n):
    """Bitsets from 2 words to 65536 words (64 tile rows of 128 words)."""
    from repro.core.bloomfilter import BloomFilter, hash_values
    from repro.kernels.bloom.ops import bloom_probe
    from repro.kernels.bloom.ref import bloom_probe_ref

    rng = np.random.default_rng(members)
    keys = rng.integers(0, 10**7, members)
    bf = BloomFilter.for_expected(members)
    bf.add(keys)
    vals = np.concatenate([keys[:n // 2], rng.integers(0, 10**7, n - n // 2)])
    h = hash_values(vals)
    h1 = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    h2 = (h >> np.uint64(32)).astype(np.uint32)
    bits = bf.bits.view(np.uint32)
    got = np.asarray(bloom_probe(h1, h2, bits, bf.num_hashes, bf.num_bits,
                                 engine="pallas"))
    exp = np.asarray(bloom_probe_ref(h1, h2, bits, bf.num_hashes,
                                     bf.num_bits))
    np.testing.assert_array_equal(got, exp)
    np.testing.assert_array_equal(got, bf.might_contain(vals))
    assert got[:min(members, n // 2)].all()  # no false negatives


def test_hash_group_matmul_asks_for_float32_precision():
    """At the default precision the TPU's MXU rounds float32 operands to
    bfloat16, so merged partial sums and counts above 256 come back
    inexact; interpret mode cannot show that, so check what is traced."""
    from repro.kernels.hash_group.hash_group import hash_group_pallas

    jaxpr = str(jax.make_jaxpr(
        lambda c, v: hash_group_pallas(c, v, 128, interpret=True))(
            jnp.zeros(1024, jnp.int32), jnp.zeros(1024, jnp.float32)))
    dots = jaxpr.split("dot_general[")[1:]
    assert dots and all(
        "precision=(Precision.HIGHEST, Precision.HIGHEST)"
        in d.split("preferred_element_type")[0] for d in dots)


# ---- where Pallas kernels run --------------------------------------------
def test_interpret_mode_follows_backend(monkeypatch):
    from repro.kernels import registry

    monkeypatch.setattr(registry, "_setup_compile_cache", lambda: None)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert registry.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert registry.interpret_mode() is False
    for other in ("gpu", "rocm"):
        monkeypatch.setattr(jax, "default_backend", lambda: other)
        with pytest.raises(RuntimeError, match=other):
            registry.interpret_mode()


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_setup(monkeypatch, env_dir):
    """The cache goes to JAX_COMPILATION_CACHE_DIR when it is set (JAX
    reads it itself) and to the checkout's fixed directory otherwise; both
    keep sub-second compiles."""
    from repro.kernels import registry

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(registry, "_cache_ready", False)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    registry._setup_compile_cache()
    if env_dir is None:
        assert updates["jax_compilation_cache_dir"] == str(
            registry.COMPILE_CACHE_DIR)
        assert registry.COMPILE_CACHE_DIR.parent.joinpath(
            "pyproject.toml").exists()
    else:
        assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


@pytest.mark.parametrize("n,floor,want", [(0, 1024, 1024), (1, 1024, 1024),
                                          (1024, 1024, 1024),
                                          (1025, 1024, 2048),
                                          (8192, 1024, 8192),
                                          (7, 128, 128), (129, 128, 256)])
def test_bucket(n, floor, want):
    from repro.kernels.registry import bucket

    assert bucket(n, floor) == want
