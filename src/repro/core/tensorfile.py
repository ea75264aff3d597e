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


def _column_stats(arr: np.ndarray) -> Dict[str, Any]:
    """Min/max/nan-count — the Iceberg-style manifest stats used for pruning
    and for cheap audit expectations."""
    if arr.size == 0 or arr.dtype.kind not in "fiub":
        return {}
    farr = arr.astype(np.float64) if arr.dtype.kind == "f" else arr
    stats: Dict[str, Any] = {}
    if arr.dtype.kind == "f":
        nan_count = int(np.isnan(farr).sum())
        stats["nan_count"] = nan_count
        if nan_count < farr.size:
            stats["min"] = float(np.nanmin(farr))
            stats["max"] = float(np.nanmax(farr))
    else:
        stats["min"] = int(arr.min()) if arr.dtype.kind in "iu" else int(arr.min())
        stats["max"] = int(arr.max()) if arr.dtype.kind in "iu" else int(arr.max())
    return stats


def encode(cols: Mapping[str, np.ndarray]) -> Tuple[bytes, Dict[str, Any]]:
    """Serialize columns → (bytes, meta).  meta carries nrows/schema/stats and
    becomes the manifest entry next to the content digest."""
    if not cols:
        raise SchemaError("tensorfile needs at least one column")
    with tracing.span("table.encode") as s:
        arrays = {k: np.ascontiguousarray(np.asarray(v))
                  for k, v in cols.items()}
        nrows = {v.shape[0] for v in arrays.values()}
        if len(nrows) != 1:
            raise SchemaError(f"ragged columns: row counts {sorted(nrows)}")
        (n,) = nrows
        schema = Schema.of(arrays)
        with tracing.span("table.pack"):
            payload = {
                "v": _FORMAT_VERSION,
                "nrows": n,
                "schema": schema.to_obj(),
                "data": {k: arrays[k].tobytes() for k in sorted(arrays)},
            }
            blob = msgpack.packb(payload, use_bin_type=True)
        with tracing.span("table.stats"):
            stats = {k: _column_stats(arrays[k]) for k in sorted(arrays)}
        meta = {
            "nrows": n,
            "schema": schema.to_obj(),
            "stats": stats,
            "nbytes": sum(a.nbytes for a in arrays.values()),
        }
        s.set(bytes=len(blob))
    return blob, meta


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
