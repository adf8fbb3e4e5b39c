"""LLAP cache + I/O elevator (§5.1), stripe files, stats sketches."""
import io
import os
import sys
import zipfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bloomfilter import BloomFilter
from repro.core.runtime.lrfu import LRFUPolicy
from repro.core.runtime.vector import VectorBatch
from repro.core.stats import HyperLogLogPP, compute_column_stats
from repro.core.storage import (
    SargPredicate,
    StripeFile,
    read_file_meta,
    write_stripe_file,
)


def test_stripe_file_roundtrip_and_sarg_skip(tmp_path):
    from repro.core.runtime.llap import LlapDaemon, LlapIO

    n = 40_000
    batch = VectorBatch({
        "k": np.arange(n, dtype=np.int64),
        "v": np.linspace(0, 1, n),
    })
    path = str(tmp_path / "f.tahoe")
    meta = write_stripe_file(path, batch, stripe_rows=8192, bloom_columns=["k"])
    assert meta.num_rows == n and len(meta.stripes) == 5

    daemon = LlapDaemon(cache_bytes=64 << 20)
    io = LlapIO(daemon)
    # predicate selecting only the first stripe -> 4 stripes skipped
    m2, out = io.read_file(path, ["k", "v"],
                           sarg_preds=[SargPredicate("k", "<", 100)])
    assert daemon.counters["stripes_skipped"] == 4
    assert out.num_rows == 8192  # stripe granularity; row filter comes later


def test_llap_cache_hits_and_mvcc_identity(tmp_path):
    from repro.core.runtime.llap import LlapDaemon, LlapIO

    batch = VectorBatch({"x": np.arange(10_000)})
    p1 = str(tmp_path / "a.tahoe")
    write_stripe_file(p1, batch)
    daemon = LlapDaemon()
    io = LlapIO(daemon)
    io.read_file(p1, ["x"])
    misses = daemon.counters["cache_misses"]
    io.read_file(p1, ["x"])
    assert daemon.counters["cache_misses"] == misses  # warm
    assert daemon.counters["cache_hits"] > 0
    # a different file with identical rows has a different content file_id:
    # cache entries never collide across file versions (MVCC at file level)
    p2 = str(tmp_path / "b.tahoe")
    write_stripe_file(p2, VectorBatch({"x": np.arange(10_000) + 1}))
    io.read_file(p2, ["x"])
    assert daemon.counters["cache_misses"] > misses


def test_llap_eviction_under_pressure(tmp_path):
    from repro.core.runtime.llap import LlapDaemon, LlapIO

    daemon = LlapDaemon(cache_bytes=200_000)  # tiny pool
    io = LlapIO(daemon)
    for i in range(6):
        p = str(tmp_path / f"f{i}.tahoe")
        # distinct content per file (identical content shares a file_id
        # and deduplicates in the cache — by design)
        write_stripe_file(p, VectorBatch({"x": np.arange(10_000) * (i + 1)}))
        io.read_file(p, ["x"])
    used, cap = daemon.cache_usage()
    assert used <= cap
    assert daemon.counters["evictions"] > 0


@pytest.mark.parametrize("llap,opens_per_scan", [(False, 2), (True, 1)])
def test_scan_opens_stripe_file_once(tmp_path, monkeypatch, llap,
                                     opens_per_scan):
    """Opening a stripe file parses its whole member index, so a scan opens
    it once for its stripes (plus once for the footer where no metadata
    cache holds it), not once per (stripe, column); a warm LLAP scan opens
    nothing."""
    import zipfile

    from repro.core.acid import PlainIO
    from repro.core.runtime.llap import LlapDaemon, LlapIO

    n = 40_000
    path = str(tmp_path / "f.tahoe")
    write_stripe_file(path, VectorBatch({"k": np.arange(n), "v": np.ones(n)}),
                      stripe_rows=4096)
    opens = []
    real_init = zipfile.ZipFile.__init__

    def counting_init(self, file, *a, **kw):
        opens.append(file)
        real_init(self, file, *a, **kw)

    io = LlapIO(LlapDaemon(cache_bytes=64 << 20)) if llap else PlainIO()
    io.read_meta(path)
    monkeypatch.setattr(zipfile.ZipFile, "__init__", counting_init)
    chunks = list(io.read_file_chunks(path, ["k", "v"]))
    assert len(chunks) == 10
    assert (np.concatenate([c.cols["k"] for c in chunks]) == np.arange(n)).all()
    assert len(opens) == opens_per_scan
    if llap:
        list(io.read_file_chunks(path, ["k", "v"]))
        assert len(opens) == opens_per_scan


def _np_load(path, name):
    with zipfile.ZipFile(path) as zf:
        return np.load(io.BytesIO(zf.read(name)), allow_pickle=False)


def _npy(arr, **kw):
    buf = io.BytesIO()
    np.lib.format.write_array(buf, arr, **kw)
    return buf.getvalue()


@pytest.mark.parametrize("values,stripe_rows", [
    (np.arange(8192, dtype=np.int64) * 7919 - 10**12, 4096),
    (np.arange(8192, dtype=np.int32) - 4000, 4096),
    (np.linspace(-1, 1, 8192), 4096),
    (np.linspace(-1, 1, 8192).astype(np.float32), 4096),
    (np.arange(8192) % 3 == 0, 4096),
    (np.array([f"city{i % 250}" for i in range(8192)]), 4096),
    (np.arange(10_000, dtype=np.int64), 4096),  # a short last stripe
    (np.empty(0, dtype=np.int64), 4096),  # a zero-row file
], ids=["int64", "int32", "float64", "float32", "bool", "unicode",
        "short_last_stripe", "zero_rows"])
def test_direct_read_equals_np_load(tmp_path, values, stripe_rows):
    """A chunk read by offset is the array ``np.load`` gives for the same
    member: dtype, shape and values, read-only (a cached chunk is shared)."""
    path = str(tmp_path / "f.tahoe")
    meta = write_stripe_file(path, VectorBatch({"x": values}),
                             stripe_rows=stripe_rows)
    with StripeFile(path) as f:
        for si in range(len(meta.stripes)):
            arr = f.read_column(si, "x")
            ref = _np_load(path, f"s{si}/x.npy")
            assert arr.dtype == ref.dtype and arr.shape == ref.shape
            assert np.array_equal(arr, ref)
            assert not arr.flags.writeable
    assert sum(s.rows for s in meta.stripes) == len(values)


def _rewrite(src, dst, compress_type, payload=None):
    """Copy a stripe file, its members stored with ``compress_type`` and
    each array's ``.npy`` bytes replaced by ``payload(array)``."""
    with zipfile.ZipFile(src) as zin, zipfile.ZipFile(dst, "w") as zout:
        for info in zin.infolist():
            data = zin.read(info)
            if payload and info.filename.endswith(".npy"):
                data = payload(np.load(io.BytesIO(data)))
            zout.writestr(info.filename, data, compress_type=compress_type)


@pytest.mark.parametrize("compress_type,payload", [
    (zipfile.ZIP_STORED, None),
    (zipfile.ZIP_LZMA, None),
    (zipfile.ZIP_DEFLATED, lambda a: _npy(a, version=(2, 0))),
    (zipfile.ZIP_DEFLATED,
     lambda a: _npy(np.asfortranarray(np.stack([a, a], axis=1)))),
    (zipfile.ZIP_DEFLATED,
     lambda a: _npy(a.astype(object), allow_pickle=True)),
    (zipfile.ZIP_DEFLATED, lambda a: _npy(a)[:-8]),
], ids=["stored", "lzma", "npy_2_0", "fortran_order", "object_dtype",
        "short_payload"])
def test_member_it_cannot_view_raises(tmp_path, compress_type, payload):
    """``write_stripe_file`` writes deflated ``.npy`` 1.0 arrays of plain
    values in C order; a member in any other form, or whose payload is not
    the length its header gives, raises ``BadZipFile``."""
    src, path = str(tmp_path / "src.tahoe"), str(tmp_path / "f.tahoe")
    write_stripe_file(src, VectorBatch({"k": np.arange(10_000) * 3}),
                      stripe_rows=4096)
    _rewrite(src, path, compress_type, payload)
    with StripeFile(path) as f, pytest.raises(zipfile.BadZipFile):
        f.read_column(0, "k")


@pytest.mark.parametrize("where", ["deflate_stream", "directory_crc"])
def test_flipped_byte_in_a_member_raises(tmp_path, where):
    """A corrupt deflate stream, or bytes whose CRC-32 is not the
    directory's, raise ``BadZipFile`` as ``zipfile`` does."""
    path = str(tmp_path / "f.tahoe")
    write_stripe_file(path, VectorBatch({"k": np.arange(8192) * 7}))
    with zipfile.ZipFile(path) as zf:
        info = zf.getinfo("s0/k.npy")
    with open(path, "rb") as fh:
        data = fh.read()
    if where == "deflate_stream":
        at = (info.header_offset + 30 + len(info.filename)
              + info.compress_size // 2)
    else:
        # the central directory's record of the member: 46 bytes, then its
        # name, which appears there last; the CRC-32 is at byte 16
        at = data.rindex(b"s0/k.npy") - 46 + 16
    with open(path, "r+b") as fh:
        fh.seek(at)
        fh.write(bytes([data[at] ^ 0xFF]))
    with StripeFile(path) as f, pytest.raises(zipfile.BadZipFile):
        f.read_column(0, "k")


def test_threads_read_one_stripe_file_at_once(tmp_path):
    """Eight threads (more where the host has more cores) reading every
    chunk of one file at once, its open included, get what one thread
    reading them in turn gets."""
    n = 30 * 1024
    path = str(tmp_path / "f.tahoe")
    rng = np.random.default_rng(7)
    meta = write_stripe_file(path, VectorBatch({
        "k": rng.integers(0, 10**9, n), "v": rng.random(n),
        "s": np.array([f"v{i % 91}" for i in range(n)])}), stripe_rows=1024)
    chunks = [(si, c) for si in range(len(meta.stripes))
              for c in ("k", "v", "s")] * 4
    with StripeFile(path) as f:
        serial = [f.read_column(si, c) for si, c in chunks]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = max(8, os.cpu_count() + 1)
        with StripeFile(path) as f, ThreadPoolExecutor(workers) as pool:
            futures = [pool.submit(f.read_column, si, c) for si, c in chunks]
            threaded = [fut.result(timeout=60) for fut in futures]
    finally:
        sys.setswitchinterval(switch)
    for arr, ref in zip(threaded, serial):
        assert arr.dtype == ref.dtype and np.array_equal(arr, ref)


def test_stripe_file_bytes_unchanged(tmp_path):
    """The write path keeps the format: one deflated member per (stripe,
    column) whose bytes are ``np.save`` of the stripe's values (a ``.npy``
    1.0 header), then the footer; such a file reads through LLAP."""
    from repro.core.runtime.llap import LlapDaemon, LlapIO
    from repro.core.storage import _META_KEY

    n = 10_000
    batch = VectorBatch({
        "k": np.arange(n, dtype=np.int64), "v": np.arange(n) * 0.25,
        "s": np.array([f"s{i % 37}" for i in range(n)])})
    path = str(tmp_path / "f.tahoe")
    write_stripe_file(path, batch, writeid=3, stripe_rows=4096,
                      bloom_columns=["k"])
    with zipfile.ZipFile(path) as zf:
        infos = zf.infolist()
        assert [i.filename for i in infos] == [
            f"s{si}/{c}.npy" for si in range(3) for c in ("k", "v", "s")
        ] + [_META_KEY]
        assert all(i.compress_type == zipfile.ZIP_DEFLATED for i in infos)
        for info in infos[:-1]:
            si, c = int(info.filename[1]), info.filename[3]
            payload = zf.read(info)
            assert payload[:8] == b"\x93NUMPY\x01\x00"
            buf = io.BytesIO()
            np.save(buf, batch.cols[c][si * 4096:(si + 1) * 4096],
                    allow_pickle=False)
            assert payload == buf.getvalue()
    daemon = LlapDaemon(cache_bytes=64 << 20)
    _, out = LlapIO(daemon).read_file(path)
    for c in batch.column_names:
        assert np.array_equal(out.cols[c], batch.cols[c])
    assert daemon.counters["cache_misses"] == 3 * 3


def test_lrfu_policy_prefers_frequent():
    pol = LRFUPolicy(lam=0.1)
    for _ in range(5):
        pol.on_access("hot")
    pol.on_access("cold")
    pol.on_access("hot")
    assert pol.victim() == "cold"


def test_hll_accuracy_and_merge():
    h1, h2 = HyperLogLogPP(12), HyperLogLogPP(12)
    for i in range(3000):
        h1.add(i)
    for i in range(2000, 5000):
        h2.add(i)
    merged = h1.merge(h2)
    assert abs(merged.cardinality() - 5000) / 5000 < 0.05
    # serialization roundtrip
    again = HyperLogLogPP.deserialize(merged.serialize())
    assert again.cardinality() == merged.cardinality()


def test_column_stats_additive(star_schema):
    st_ = star_schema.hms.get_stats("store_sales")
    assert st_.row_count == 8000
    cs = st_.columns["ss_customer_sk"]
    assert abs(cs.ndv - 300) / 300 < 0.06


@settings(max_examples=25, deadline=None)
@given(members=st.sets(st.integers(0, 10_000), min_size=1, max_size=300),
       probes=st.lists(st.integers(0, 10_000), min_size=1, max_size=100))
def test_property_bloom_no_false_negatives(members, probes):
    bf = BloomFilter.for_expected(len(members))
    bf.add(np.array(sorted(members)))
    got = bf.might_contain(np.array(probes))
    for p, g in zip(probes, got):
        if p in members:
            assert g  # bloom filters never produce false negatives
