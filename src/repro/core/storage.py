"""ORC-like columnar stripe files (paper §2 "Data storage", §5.1 I/O elevator).

Each data file is a zip of column arrays organized in *stripes* (row groups)
plus a JSON footer with per-stripe, per-column min/max statistics and optional
bloom filters.  This gives the scan path the two structures the paper's I/O
elevator pushes down: sargable predicates (min/max seek) and bloom filters
(paper §4.6, §5.1).

Files are immutable once written (HDFS/object-store semantics).  Every file
carries a content-derived ``file_id`` which plays the role of the HDFS unique
file id / S3 ETag that LLAP uses for cache validity (paper §5.1).
"""
from __future__ import annotations

import hashlib
import io
import json
import math
import os
import struct
import zipfile
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.lockdep import make_lock
from .bloomfilter import BloomFilter
from .runtime.vector import VectorBatch

DEFAULT_STRIPE_ROWS = 8192
_META_KEY = "_tahoe_meta.json"


@dataclass
class StripeMeta:
    rows: int
    # col -> {"min": x, "max": x} (present when the column is orderable)
    ranges: Dict[str, dict] = field(default_factory=dict)
    blooms: Dict[str, dict] = field(default_factory=dict)  # col -> BloomFilter dict


@dataclass
class FileMeta:
    file_id: str
    num_rows: int
    columns: List[str]
    dtypes: Dict[str, str]
    stripes: List[StripeMeta]
    writeid: int = 0

    def to_json(self) -> str:
        return json.dumps(
            {
                "file_id": self.file_id,
                "num_rows": self.num_rows,
                "columns": self.columns,
                "dtypes": self.dtypes,
                "writeid": self.writeid,
                "stripes": [
                    {"rows": s.rows, "ranges": s.ranges, "blooms": s.blooms}
                    for s in self.stripes
                ],
            }
        )

    @classmethod
    def from_json(cls, s: str) -> "FileMeta":
        d = json.loads(s)
        return cls(
            file_id=d["file_id"],
            num_rows=d["num_rows"],
            columns=d["columns"],
            dtypes=d["dtypes"],
            writeid=d.get("writeid", 0),
            stripes=[
                StripeMeta(x["rows"], x.get("ranges", {}), x.get("blooms", {}))
                for x in d["stripes"]
            ],
        )


def _col_range(values: np.ndarray) -> Optional[dict]:
    if len(values) == 0:
        return None
    if values.dtype.kind in ("i", "u", "f"):
        if values.dtype.kind == "f":
            valid = values[~np.isnan(values)]
            if len(valid) == 0:
                return None
            return {"min": float(valid.min()), "max": float(valid.max())}
        return {"min": int(values.min()), "max": int(values.max())}
    if values.dtype.kind in ("U", "S"):
        s = np.sort(values)  # np.min lacks a unicode ufunc loop
        return {"min": str(s[0]), "max": str(s[-1])}
    return None


def write_stripe_file(
    path: str,
    batch: VectorBatch,
    *,
    writeid: int = 0,
    stripe_rows: int = DEFAULT_STRIPE_ROWS,
    bloom_columns: Sequence[str] = (),
) -> FileMeta:
    """Write a batch as an immutable stripe file; returns its metadata."""
    columns = batch.column_names
    n = batch.num_rows
    stripes: List[StripeMeta] = []
    hasher = hashlib.blake2b(digest_size=10)

    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", compression=zipfile.ZIP_DEFLATED) as zf:
        for si, start in enumerate(range(0, max(n, 1), stripe_rows)):
            chunk = batch.slice(start, min(start + stripe_rows, n))
            if chunk.num_rows == 0 and n > 0:
                break
            meta = StripeMeta(rows=chunk.num_rows)
            for col in columns:
                values = chunk.cols[col]
                arr_buf = io.BytesIO()
                np.save(arr_buf, values, allow_pickle=False)
                payload = arr_buf.getvalue()
                hasher.update(payload)
                zf.writestr(f"s{si}/{col}.npy", payload)
                rng = _col_range(values)
                if rng is not None:
                    meta.ranges[col] = rng
                if col in bloom_columns and len(values):
                    bf = BloomFilter.for_expected(len(values))
                    bf.add(values)
                    meta.blooms[col] = bf.to_dict()
            stripes.append(meta)
            if n == 0:
                break
        fmeta = FileMeta(
            file_id=hasher.hexdigest(),
            num_rows=n,
            columns=columns,
            dtypes={c: str(batch.cols[c].dtype) for c in columns},
            stripes=stripes,
            writeid=writeid,
        )
        zf.writestr(_META_KEY, fmeta.to_json())

    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(buf.getvalue())
    os.replace(tmp, path)  # atomic publish, mimicking HDFS rename semantics
    return fmeta


def read_file_meta(path: str) -> FileMeta:
    """Footer-only read — this is what LLAP's bulk metadata cache loads."""
    with zipfile.ZipFile(path) as zf:
        return FileMeta.from_json(zf.read(_META_KEY).decode())


_NPY_V1 = b"\x93NUMPY\x01\x00"


def _npy_header(name: str, header: bytes) -> Tuple[np.dtype, tuple]:
    """The dtype and shape a ``.npy`` 1.0 header gives, for an array
    ``np.frombuffer`` can view (C order, no objects)."""
    try:
        shape, fortran, dtype = np.lib.format.read_array_header_1_0(
            io.BytesIO(header[len(_NPY_V1):]))
    except ValueError as e:
        raise zipfile.BadZipFile(f"Bad .npy header in {name!r}") from e
    if fortran or dtype.hasobject:
        raise zipfile.BadZipFile(f"{name!r} is not a C-order array of "
                                 f"plain values")
    return dtype, shape


class StripeFile:
    """A stripe file opened for column reads.

    A file holds one member per (stripe, column) as ``write_stripe_file``
    writes it: a deflated ``.npy`` 1.0 array.  It opens at the first read,
    once per scan, so a scan served from cache opens nothing; the open
    parses the zip's central directory, which indexes every member by its
    offset, sizes and CRC-32, and is the only step under the lock.  A read
    takes the member's bytes by offset (``os.pread``, no shared file
    position), inflates them (``zlib`` releases the GIL), checks size and
    CRC against the directory and views the payload with ``np.frombuffer``,
    its header parsed once per distinct header.  So I/O threads read side
    by side.  A member in any other form raises ``zipfile.BadZipFile``.
    The arrays are read-only: a chunk is shared by every query that scans
    it.
    """

    def __init__(self, path: str):
        self._path = path
        self._file = None
        self._zf: Optional[zipfile.ZipFile] = None
        self._headers: Dict[bytes, Tuple[np.dtype, tuple]] = {}
        self._lock = make_lock("storage.stripe_file")

    def _open(self) -> zipfile.ZipFile:
        if self._zf is None:
            with self._lock:
                if self._zf is None:
                    f = open(self._path, "rb")
                    try:
                        zf = zipfile.ZipFile(f)
                    except BaseException:
                        f.close()
                        raise
                    self._file, self._zf = f, zf
        return self._zf

    def read_column(self, stripe: int, column: str) -> np.ndarray:
        info = self._open().getinfo(f"s{stripe}/{column}.npy")
        return self._view(info.filename, self._member_bytes(info))

    def _member_bytes(self, info: zipfile.ZipInfo) -> bytes:
        """The member's bytes, inflated and checked against the directory's
        size and CRC-32, as ``zipfile`` checks them."""
        if info.flag_bits & 0x1 or info.compress_type != zipfile.ZIP_DEFLATED:
            raise zipfile.BadZipFile(
                f"{info.filename!r} is not a plain deflated member")
        fd = self._file.fileno()
        # the local header (30 bytes, then name and extra field) and the
        # data, in one read where the header has no extra field
        head = 30 + len(info.orig_filename.encode("utf-8"))
        buf = os.pread(fd, head + info.compress_size, info.header_offset)
        if buf[:4] != b"PK\x03\x04":
            raise zipfile.BadZipFile(
                f"Bad magic number for file header of {info.filename!r}")
        name_len, extra_len = struct.unpack_from("<HH", buf, 26)
        start = 30 + name_len + extra_len
        if start == head and len(buf) == head + info.compress_size:
            raw = memoryview(buf)[start:]
        else:
            raw = os.pread(fd, info.compress_size, info.header_offset + start)
        try:
            data = zlib.decompress(raw, -15, info.file_size)
        except zlib.error as e:
            raise zipfile.BadZipFile(
                f"Bad deflate stream for file {info.filename!r}") from e
        if len(data) != info.file_size or zlib.crc32(data) != info.CRC:
            raise zipfile.BadZipFile(f"Bad CRC-32 for file {info.filename!r}")
        return data

    def _view(self, name: str, data: bytes) -> np.ndarray:
        """The ``.npy`` 1.0 payload as a read-only array over ``data``."""
        if data[:len(_NPY_V1)] != _NPY_V1:
            raise zipfile.BadZipFile(f"{name!r} is not a .npy 1.0 array")
        start = 10 + int.from_bytes(data[8:10], "little")
        header = data[:start]
        parsed = self._headers.get(header)
        if parsed is None:
            parsed = self._headers[header] = _npy_header(name, header)
        dtype, shape = parsed
        count = math.prod(shape)
        if len(data) - start != count * dtype.itemsize:
            raise zipfile.BadZipFile(
                f"{name!r} holds {len(data) - start} payload bytes, not "
                f"the {count * dtype.itemsize} its header gives")
        return np.frombuffer(data, dtype, count, start).reshape(shape)

    def close(self) -> None:
        with self._lock:
            if self._zf is not None:
                self._zf.close()
                self._file.close()
                self._zf = self._file = None

    def __enter__(self) -> "StripeFile":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# --------------------------------------------------------------------------
# Sargable predicates: (column, op, literal) triples the I/O elevator can use
# against stripe min/max ranges and bloom filters to skip row groups.
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class SargPredicate:
    column: str
    op: str  # one of <, <=, >, >=, =, in
    value: object


def stripe_may_match(meta: StripeMeta, preds: Sequence[SargPredicate]) -> bool:
    for p in preds:
        rng = meta.ranges.get(p.column)
        if rng is not None:
            lo, hi = rng["min"], rng["max"]
            if p.op == "=" and not (lo <= p.value <= hi):
                return False
            if p.op == "<" and not (lo < p.value):
                return False
            if p.op == "<=" and not (lo <= p.value):
                return False
            if p.op == ">" and not (hi > p.value):
                return False
            if p.op == ">=" and not (hi >= p.value):
                return False
            if p.op == "in" and not any(lo <= v <= hi for v in p.value):
                return False
        bloom_d = meta.blooms.get(p.column)
        if bloom_d is not None and p.op == "=":
            bf = BloomFilter.from_dict(bloom_d)
            if not bool(bf.might_contain(np.asarray([p.value]))[0]):
                return False
    return True
