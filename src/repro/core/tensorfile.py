"""TensorFile — the "Parquet" of the tensor lake (Fig. 2, layer 2).

An immutable, schema-carrying, columnar container for a batch of rows whose
columns are ndarrays (scalars per row or fixed-shape tensors per row).  It is
the unit of content addressing: tables are manifests of tensor-file digests.

Differences from Parquet are deliberate TPU adaptations (see DESIGN.md §2):
columns are dense ndarrays (directly device-puttable), not Arrow buffers.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import msgpack
import numpy as np

from .. import tracing
from .errors import SchemaError

try:  # bfloat16 & friends come with jax
    import ml_dtypes

    _EXTRA_DTYPES = {
        "bfloat16": np.dtype(ml_dtypes.bfloat16),
        "float8_e4m3fn": np.dtype(ml_dtypes.float8_e4m3fn),
        "float8_e5m2": np.dtype(ml_dtypes.float8_e5m2),
    }
except ImportError:  # pragma: no cover
    _EXTRA_DTYPES = {}

_FORMAT_VERSION = 1


def resolve_dtype(name: str) -> np.dtype:
    if name in _EXTRA_DTYPES:
        return _EXTRA_DTYPES[name]
    return np.dtype(name)


@dataclass(frozen=True)
class ColumnSpec:
    """Schema entry: per-row shape (without the leading row axis) + dtype."""

    name: str
    dtype: str
    row_shape: Tuple[int, ...]

    def to_obj(self) -> list:
        return [self.name, self.dtype, list(self.row_shape)]

    @staticmethod
    def from_obj(obj: list) -> "ColumnSpec":
        return ColumnSpec(obj[0], obj[1], tuple(obj[2]))


@dataclass(frozen=True)
class Schema:
    columns: Tuple[ColumnSpec, ...]

    def to_obj(self) -> list:
        return [c.to_obj() for c in self.columns]

    @staticmethod
    def from_obj(obj: list) -> "Schema":
        return Schema(tuple(ColumnSpec.from_obj(o) for o in obj))

    @staticmethod
    def of(cols: Mapping[str, np.ndarray]) -> "Schema":
        specs = []
        for name in sorted(cols):
            arr = np.asarray(cols[name])
            if arr.ndim == 0:
                raise SchemaError(f"column {name!r} must have a row axis")
            specs.append(ColumnSpec(name, arr.dtype.name, tuple(arr.shape[1:])))
        return Schema(tuple(specs))

    def names(self) -> List[str]:
        return [c.name for c in self.columns]

    def check_compatible(self, other: "Schema") -> None:
        if self != other:
            raise SchemaError(f"schema mismatch:\n  {self}\n  {other}")

    def project(self, names) -> "Schema":
        keep = set(names)
        return Schema(tuple(c for c in self.columns if c.name in keep))


#: elements per slice of the NaN-counting pass (its temporaries stay small)
_NAN_CHUNK = 1 << 20


def _column_stats(arr: np.ndarray) -> Dict[str, Any]:
    """Min/max/nan-count — the Iceberg-style manifest stats used for pruning
    and for cheap audit expectations.

    Floats are reduced in their own dtype, with no copy: every value
    converts exactly to float64, so the extremes are the ones a float64
    copy gives.  Only a zero's sign can depend on the order of a
    reduction, and the stats are packed into content-addressed manifests:
    where both -0.0 and 0.0 are present and a zero is an extreme, the
    float64 copy decides, as it did for every float column before."""
    if arr.size == 0 or arr.dtype.kind not in "fiub":
        return {}
    if arr.dtype.kind != "f":
        return {"min": int(arr.min()), "max": int(arr.max())}
    flat = arr.reshape(-1)
    lo = flat.min()  # NaN propagates: a number here means no NaN
    nan_count = 0
    if np.isnan(lo):
        nan_count = sum(int(np.count_nonzero(np.isnan(flat[i:i + _NAN_CHUNK])))
                        for i in range(0, flat.size, _NAN_CHUNK))
        if nan_count == flat.size:
            return {"nan_count": nan_count}
        with np.errstate(invalid="ignore"):  # ml_dtypes' fmin warns on NaN
            lo, hi = np.fmin.reduce(flat), np.fmax.reduce(flat)
    else:
        hi = flat.max()
    if (lo == 0 or hi == 0) and _both_zero_signs(flat):
        wide = arr.astype(np.float64)
        lo, hi = np.nanmin(wide), np.nanmax(wide)
    return {"nan_count": nan_count, "min": float(lo), "max": float(hi)}


def _both_zero_signs(flat: np.ndarray) -> bool:
    """Whether a float column holds both 0.0 and -0.0.  Read as unsigned
    integers 0.0 is the least value; read as signed, -0.0 is.  A width
    with no integer view answers yes."""
    size = flat.dtype.itemsize
    if size not in (1, 2, 4, 8):
        return True
    signed = flat.view(f"i{size}")
    return bool(flat.view(f"u{size}").min() == 0
                and signed.min() == np.iinfo(signed.dtype).min)


def _bin_header(n: int) -> bytes:
    """msgpack's bin8/bin16/bin32 header for ``n`` bytes."""
    if n < 1 << 8:
        return b"\xc4" + n.to_bytes(1, "big")
    if n < 1 << 16:
        return b"\xc5" + n.to_bytes(2, "big")
    if n < 1 << 32:
        return b"\xc6" + n.to_bytes(4, "big")
    raise SchemaError(f"column of {n} bytes exceeds msgpack's 4 GiB bin")


def encode_parts(cols: Mapping[str, np.ndarray]
                 ) -> Tuple[List[Any], Dict[str, Any]]:
    """Serialize columns → (parts, meta) without copying the columns.

    ``b"".join(parts)`` is byte for byte the blob :func:`encode` returns:
    the same msgpack map, with each column's ``bin`` header written here
    and its data a view of the column's own memory (a column that is not
    C-contiguous is copied once, and counted in the span's ``copied``).
    The store hashes, compresses and writes the parts as they are
    (``put_parts``).  meta carries nrows/schema/stats and becomes the
    manifest entry next to the content digest."""
    if not cols:
        raise SchemaError("tensorfile needs at least one column")
    with tracing.span("table.encode") as s:
        arrays = {k: np.asarray(v) for k, v in cols.items()}
        copied = sum(a.nbytes for a in arrays.values()
                     if not a.flags.c_contiguous)
        arrays = {k: np.ascontiguousarray(a) for k, a in arrays.items()}
        nrows = {v.shape[0] for v in arrays.values()}
        if len(nrows) != 1:
            raise SchemaError(f"ragged columns: row counts {sorted(nrows)}")
        (n,) = nrows
        schema = Schema.of(arrays)
        with tracing.span("table.pack"):
            packer = msgpack.Packer(use_bin_type=True)
            head = [packer.pack_map_header(4),
                    packer.pack("v"), packer.pack(_FORMAT_VERSION),
                    packer.pack("nrows"), packer.pack(n),
                    packer.pack("schema"), packer.pack(schema.to_obj()),
                    packer.pack("data"), packer.pack_map_header(len(arrays))]
            parts: List[Any] = [b"".join(head)]
            for k in sorted(arrays):
                data = memoryview(arrays[k].reshape(-1).view(np.uint8))
                parts.append(packer.pack(k) + _bin_header(data.nbytes))
                parts.append(data)
        with tracing.span("table.stats") as st:
            stats = {k: _column_stats(arrays[k]) for k in sorted(arrays)}
            st.set(nan_columns=sum(v.get("nan_count", 0) > 0
                                   for v in stats.values()))
        meta = {
            "nrows": n,
            "schema": schema.to_obj(),
            "stats": stats,
            "nbytes": sum(a.nbytes for a in arrays.values()),
        }
        s.set(bytes=sum(memoryview(p).nbytes for p in parts), copied=copied)
    return parts, meta


def encode(cols: Mapping[str, np.ndarray]) -> Tuple[bytes, Dict[str, Any]]:
    """:func:`encode_parts` joined into one ``bytes`` blob."""
    parts, meta = encode_parts(cols)
    return b"".join(parts), meta


def decode(blob: bytes, columns: Optional[Sequence[str]] = None
           ) -> Dict[str, np.ndarray]:
    """Deserialize a tensorfile.  With ``columns``, only the named columns
    are materialized — the other columns' bytes are never touched, which
    is what makes projected table scans cheap (``TableIO.read(columns=)``
    pushes its selection down to here)."""
    payload = msgpack.unpackb(blob, raw=False)
    if payload.get("v") != _FORMAT_VERSION:
        raise SchemaError(f"unknown tensorfile version {payload.get('v')!r}")
    schema = Schema.from_obj(payload["schema"])
    n = payload["nrows"]
    specs = schema.columns
    if columns is not None:
        by_name = {spec.name: spec for spec in specs}
        missing = sorted(set(columns) - set(by_name))
        if missing:
            raise SchemaError(f"missing columns {missing}")
        specs = [by_name[name] for name in dict.fromkeys(columns)]
    out: Dict[str, np.ndarray] = {}
    for spec in specs:
        raw = payload["data"][spec.name]
        arr = np.frombuffer(raw, dtype=resolve_dtype(spec.dtype))
        out[spec.name] = arr.reshape((n, *spec.row_shape)).copy()
    return out


def concat(frames: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Row-concatenate decoded tensorfiles (the table read path)."""
    if not frames:
        return {}
    names = frames[0].keys()
    for f in frames[1:]:
        if f.keys() != names:
            raise SchemaError("cannot concat frames with different columns")
    return {k: np.concatenate([f[k] for f in frames], axis=0) for k in names}
