"""The tensorfile as a sequence of parts: byte identity with the blob
format, the copy counter, and column stats in the column's own dtype.

A tensorfile's sha256 is its object's address, and its stats are packed
into content-addressed manifests, so both are pinned against the
algorithms the format was first written with (copied here as references).
"""

import math
import tracemalloc

import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro import tracing
from repro.core import ObjectStore, sha256_hex
from repro.core import tensorfile as tf
from repro.core.table import TableIO, _unpack


# ---------------------------------------------------------------- references
def reference_blob(cols):
    """The tensorfile blob as the format was first encoded: each column's
    ``tobytes`` inside one ``msgpack.packb`` of the whole map."""
    arrays = {k: np.ascontiguousarray(np.asarray(v)) for k, v in cols.items()}
    (n,) = {v.shape[0] for v in arrays.values()}
    return msgpack.packb({
        "v": 1,
        "nrows": n,
        "schema": tf.Schema.of(arrays).to_obj(),
        "data": {k: arrays[k].tobytes() for k in sorted(arrays)},
    }, use_bin_type=True)


def reference_stats(arr):
    """Column stats as first computed: through a float64 copy."""
    if arr.size == 0 or arr.dtype.kind not in "fiub":
        return {}
    farr = arr.astype(np.float64) if arr.dtype.kind == "f" else arr
    stats = {}
    if arr.dtype.kind == "f":
        nan_count = int(np.isnan(farr).sum())
        stats["nan_count"] = nan_count
        if nan_count < farr.size:
            stats["min"] = float(np.nanmin(farr))
            stats["max"] = float(np.nanmax(farr))
    else:
        stats["min"] = int(arr.min())
        stats["max"] = int(arr.max())
    return stats


def assert_same_stats(got, want):
    """Equal dicts, in the same key order (msgpack keeps it), with the same
    sign on every zero."""
    assert list(got.items()) == list(want.items())
    for k, v in want.items():
        if isinstance(v, float):
            assert type(got[k]) is float
            assert math.copysign(1.0, got[k]) == math.copysign(1.0, v), k


def last_span(name):
    return [s for s in tracing.spans() if s.name == name][-1]


# ------------------------------------------------------------- byte identity
DTYPES = [np.float16, np.float32, np.float64, ml_dtypes.bfloat16,
          ml_dtypes.float8_e4m3fn, ml_dtypes.float8_e5m2,
          np.int8, np.int16, np.int32, np.int64,
          np.uint8, np.uint16, np.uint32, np.uint64, np.bool_]


def _column(rng, dtype, shape):
    if np.dtype(dtype) == np.bool_:
        return rng.integers(0, 2, size=shape).astype(bool)
    if np.dtype(dtype).kind in "iu":
        info = np.iinfo(dtype)
        return rng.integers(info.min, info.max, size=shape, dtype=dtype,
                            endpoint=True)
    return (rng.standard_normal(shape) * 3).astype(dtype)


@pytest.mark.parametrize("ncols", [1, 2, 3, 4])
@pytest.mark.parametrize("row_shape", [(), (3,), (2, 5)], ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_parts_join_to_the_blob(dtype, row_shape, ncols):
    """``b"".join(encode_parts(c))`` is the blob byte for byte, ``encode``
    returns the same, and ``decode`` gives the columns back."""
    rng = np.random.default_rng(ncols * 100 + len(row_shape))
    n = 7
    # the first column has the dtype under test, the rest cycle the list
    names = ["w", "a", "z", "m"][:ncols]
    start = DTYPES.index(dtype)
    cols = {name: _column(rng, DTYPES[(start + i) % len(DTYPES)],
                          (n, *row_shape))
            for i, name in enumerate(names)}
    parts, meta = tf.encode_parts(cols)
    blob = b"".join(parts)
    assert blob == reference_blob(cols)
    assert tf.encode(cols)[0] == blob
    assert last_span("table.encode").attrs == {"bytes": len(blob),
                                               "copied": 0}
    out = tf.decode(blob)
    assert sorted(out) == sorted(cols)
    for name, arr in cols.items():
        assert out[name].dtype == arr.dtype
        assert out[name].tobytes() == arr.tobytes()
        assert_same_stats(meta["stats"][name], reference_stats(arr))
    assert meta["nrows"] == n
    assert meta["nbytes"] == sum(a.nbytes for a in cols.values())


@pytest.mark.parametrize("nbytes", [0, 1, 255, 256, 65_535, 65_536, 70_001])
def test_bin_header_edges(nbytes):
    """Columns at the bin8/bin16/bin32 boundaries of msgpack's format."""
    rng = np.random.default_rng(nbytes)
    cols = {"x": rng.integers(0, 256, size=nbytes, dtype=np.uint8),
            "y": np.zeros(nbytes, np.bool_)}
    blob = b"".join(tf.encode_parts(cols)[0])
    assert blob == reference_blob(cols)
    out = tf.decode(blob)
    np.testing.assert_array_equal(out["x"], cols["x"])


@pytest.mark.parametrize("layout", ["strided", "fortran", "transposed_rows"])
def test_non_contiguous_columns_are_copied_and_counted(layout):
    rng = np.random.default_rng(3)
    base = rng.standard_normal((6, 8)).astype(np.float32)
    odd = {"strided": base[:, ::2],
           "fortran": np.asfortranarray(base),
           "transposed_rows": base.reshape(6, 2, 4).transpose(0, 2, 1)}[layout]
    assert not odd.flags.c_contiguous
    cols = {"odd": odd, "even": np.arange(6, dtype=np.int64)}
    parts, meta = tf.encode_parts(cols)
    assert b"".join(parts) == reference_blob(cols)
    assert last_span("table.encode").attrs["copied"] == odd.nbytes
    np.testing.assert_array_equal(tf.decode(b"".join(parts))["odd"], odd)


def test_parts_reference_the_columns():
    """A contiguous column's data part is a view of its memory."""
    col = np.arange(1000, dtype=np.float32).reshape(1, 1000)
    parts, _ = tf.encode_parts({"c": col})
    views = [p for p in parts if isinstance(p, memoryview)]
    assert len(views) == 1
    assert np.shares_memory(np.frombuffer(views[0], np.uint8), col)


# ------------------------------------------------------------- pinned format
def pinned_table():
    rng = np.random.default_rng(20261018)
    n = 40
    f32 = rng.standard_normal((n, 3)).astype(np.float32)
    f32[3, 1] = np.nan
    f32[5, 0] = -0.0
    f32[7, 2] = np.inf
    nz = np.abs(rng.standard_normal(n)).astype(np.float32)
    nz[[2, 20, 33]] = -0.0
    return {
        "f32": f32,
        "f16": rng.standard_normal(n).astype(np.float16),
        "f64": rng.standard_normal((n, 2, 2)),
        "nz": nz,
        "bf16": rng.standard_normal((n, 4)).astype(ml_dtypes.bfloat16),
        "i8": rng.integers(-128, 128, n).astype(np.int8),
        "u16": rng.integers(0, 2**16, n).astype(np.uint16),
        "i64": rng.integers(-2**40, 2**40, n),
        "b": rng.integers(0, 2, n).astype(bool),
    }


def test_pinned_tensorfile_and_manifest_digests(tmp_path):
    """Digests of a seeded table, written when the tensorfile was one
    ``msgpack.packb`` and its stats came from a float64 copy: a changed
    byte would break dedup and history across versions."""
    cols = pinned_table()
    assert sha256_hex(tf.encode(cols)[0]) == (
        "238717c846e8ece977a1742d432f99de5663f55558dcc63847986adca9122b23")
    io = TableIO(ObjectStore(tmp_path), target_rows_per_file=16)
    snap = io.write_snapshot(cols)
    (manifest,) = io.load_snapshot(snap).manifests
    files = [e[0] for e in _unpack(io.store.get(manifest.digest))["entries"]]
    assert files == [
        "09b017074f055bf724e465868e7c47badabe547ac25e96f458adde98a4fd2d58",
        "53a18de5f9c74904df17277c95e69548c290f6a271493edf3aeb1c9b442d1563",
        "c4a55de077dedc411f75f42acc6bf0e387efe980aed0758fa546e27bcbbfcd30"]
    assert manifest.digest == (
        "e304e6d0ef774f4265503029d6759329e8e28a09ef80188c28ee20472ebb3af2")
    assert snap == (
        "b30df89f3160a47491c5706354f93c76ffe60834a1664e84bbaa6fd59abc03fc")


# --------------------------------------------------------------------- stats
#: float8_e5m2 is the one ml_dtypes float of numpy kind "f": it has stats
FLOATS = [np.float16, np.float32, np.float64, ml_dtypes.float8_e5m2]


def _stats_cases():
    nan, inf = np.nan, np.inf
    return {
        "plain": [1.5, -2.25, 3.0, 0.5],
        "mixed_nan": [nan, 1.0, nan, -4.0, 2.0],
        "leading_nan": [nan, 0.5],
        "all_nan": [nan, nan, nan],
        "infs": [inf, -inf, 1.0],
        "inf_and_nan": [nan, inf, -1.0],
        "pos_zero_only": [0.0, 1.0, 0.0],
        "neg_zero_only": [-0.0, 2.0, -0.0],
        "neg_zero_max": [-1.0, -0.0, -3.0],
        "mixed_zero_min": [0.0, -0.0, 1.0, -0.0, 0.0, 2.0],
        "mixed_zero_max": [-1.0, 0.0, -0.0, -2.0],
        "mixed_zero_nan": [nan, -0.0, 0.0, 3.0],
        "all_zero_mixed": [-0.0, 0.0],
        "single": [7.0],
        "single_neg_zero": [-0.0],
    }


@pytest.mark.parametrize("case", sorted(_stats_cases()))
@pytest.mark.parametrize("dtype", FLOATS, ids=lambda d: np.dtype(d).name)
def test_stats_equal_the_float64_reference(dtype, case):
    arr = np.array(_stats_cases()[case], dtype=dtype).reshape(-1, 1)
    assert_same_stats(tf._column_stats(arr), reference_stats(arr))


@pytest.mark.parametrize("dtype", FLOATS, ids=lambda d: np.dtype(d).name)
def test_stats_zero_signs_match_the_reference_at_every_length(dtype):
    """Where both zeros are present, which one a reduction returns depends
    on its order: the stats must give the float64 reduction's answer."""
    rng = np.random.default_rng(11)
    values = np.array([-0.0, 0.0, 1.0, 2.0, -1.0, np.nan], dtype=dtype)
    for size in range(1, 300, 7):
        for mix in (values[:2], values[:4], values[[0, 1, 4]], values):
            arr = rng.choice(mix, size)
            assert_same_stats(tf._column_stats(arr), reference_stats(arr))


def test_stats_nan_count_across_chunks(monkeypatch):
    monkeypatch.setattr(tf, "_NAN_CHUNK", 5)
    rng = np.random.default_rng(5)
    arr = rng.standard_normal(103).astype(np.float32)
    arr[rng.choice(103, 17, replace=False)] = np.nan
    assert_same_stats(tf._column_stats(arr), reference_stats(arr))


@pytest.mark.parametrize("dtype", [np.float32, np.int64, np.bool_,
                                   ml_dtypes.bfloat16],
                         ids=lambda d: np.dtype(d).name)
def test_stats_of_empty_columns(dtype):
    arr = np.zeros((0, 3), dtype=dtype)
    assert tf._column_stats(arr) == reference_stats(arr) == {}


@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, ml_dtypes.float8_e4m3fn],
                         ids=lambda d: np.dtype(d).name)
def test_stats_of_kind_v_floats_stay_empty(dtype):
    arr = np.array([[1.0, np.nan], [-2.0, 0.0]], dtype=dtype)
    assert tf._column_stats(arr) == reference_stats(arr) == {}


@pytest.mark.parametrize("dtype", [np.int8, np.uint64, np.int64, np.bool_],
                         ids=lambda d: np.dtype(d).name)
def test_stats_of_integer_and_bool_columns(dtype):
    arr = _column(np.random.default_rng(2), dtype, (50, 2))
    assert_same_stats(tf._column_stats(arr), reference_stats(arr))


@pytest.mark.parametrize("with_nan", [False, True])
def test_stats_allocate_nothing_the_size_of_the_column(with_nan):
    arr = np.random.default_rng(0).standard_normal(1 << 22).astype(np.float32)
    if with_nan:
        arr[::1000] = np.nan
    tracemalloc.start()
    try:
        stats = tf._column_stats(arr)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert stats["nan_count"] == (len(arr[::1000]) if with_nan else 0)
    assert peak < arr.nbytes // 4


def test_stats_span_counts_nan_columns():
    cols = {"a": np.array([1.0, np.nan], np.float32),
            "b": np.array([1.0, 2.0], np.float32),
            "c": np.array([np.nan, np.nan]),
            "d": np.array([1, 2])}
    tf.encode_parts(cols)
    assert last_span("table.stats").attrs == {"nan_columns": 2}
