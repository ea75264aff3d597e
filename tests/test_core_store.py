"""Unit + property tests for the content-addressed store and tensorfiles."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dep — fall back to the seeded mini-sampler
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from repro.core import ObjectStore, SchemaError, sha256_hex
from repro.core import store as store_mod
from repro.core.errors import ObjectNotFound, RefConflict, RefNotFound
from repro.core import tensorfile as tf


# --------------------------------------------------------------------- store
def test_put_get_roundtrip(tmp_path):
    store = ObjectStore(tmp_path)
    data = b"hello tensor lake" * 100
    digest = store.put(data)
    assert digest == sha256_hex(data)
    assert store.get(digest) == data
    assert store.has(digest)
    assert store.size(digest) < len(data)  # zstd compressed


def test_put_is_idempotent_dedup(tmp_path):
    store = ObjectStore(tmp_path)
    d1 = store.put(b"x" * 1000)
    d2 = store.put(b"x" * 1000)
    assert d1 == d2
    assert list(store.iter_objects()) == [d1]


def test_missing_object_raises(tmp_path):
    store = ObjectStore(tmp_path)
    with pytest.raises(ObjectNotFound):
        store.get("0" * 64)


def test_refs_cas(tmp_path):
    store = ObjectStore(tmp_path)
    store.set_ref("head", "aaa")
    assert store.get_ref("head") == "aaa"
    store.cas_ref("head", "aaa", "bbb")
    assert store.get_ref("head") == "bbb"
    with pytest.raises(RefConflict):
        store.cas_ref("head", "aaa", "ccc")  # stale expectation
    with pytest.raises(RefNotFound):
        store.get_ref("nope")


def test_small_objects_stored_raw(tmp_path):
    store = ObjectStore(tmp_path)
    d = store.put(b"tiny")
    assert store.get(d) == b"tiny"


# ------------------------------------------------------------------- codecs
CODECS = ["raw", "zlib"] + (["zstd"] if "zstd" in store_mod.WRITE_CODECS
                            else [])


@pytest.mark.parametrize("codec", CODECS)
def test_codec_roundtrip(tmp_path, codec):
    store = ObjectStore(tmp_path, codec=codec)
    for data in (b"", b"tiny", b"x" * 10_000, bytes(range(256)) * 64):
        digest = store.put(data)
        assert store.get(digest) == data


@settings(max_examples=40, deadline=None)
@given(data=st.binary(min_size=0, max_size=4096),
       codec=st.sampled_from(CODECS))
def test_property_codec_roundtrip_identity(tmp_path_factory, data, codec):
    """put/get is the identity under every writable codec, and the digest is
    codec-independent (content addressing hashes UNcompressed bytes)."""
    store = ObjectStore(tmp_path_factory.mktemp("s"), codec=codec)
    digest = store.put(data)
    assert digest == sha256_hex(data)
    assert store.get(digest) == data


def test_blobs_readable_across_codec_choices(tmp_path):
    """A store dir written with one codec stays readable when reopened with
    another — the codec byte in the framing decides per blob."""
    payloads = [b"alpha" * 100, b"beta" * 999, b"g"]
    digests = []
    for codec, data in zip(CODECS, payloads):
        digests.append(ObjectStore(tmp_path, codec=codec).put(data))
    for codec in CODECS:
        reader = ObjectStore(tmp_path, codec=codec)
        for digest, data in zip(digests, payloads):
            assert reader.get(digest) == data


def test_unknown_codec_rejected(tmp_path):
    with pytest.raises(ValueError):
        ObjectStore(tmp_path, codec="lz4")


def test_zstd_range_level_works_on_zlib_fallback(tmp_path):
    """Levels 10-22 are valid for zstd; the zlib path must clamp, not crash."""
    store = ObjectStore(tmp_path, codec="zlib", level=19)
    data = b"y" * 10_000
    assert store.get(store.put(data)) == data


# ----------------------------------------------------------- part sequences
def _content(size):
    """Half noise, half zeros: compressible, and not trivially so."""
    rng = np.random.default_rng(size)
    return rng.integers(0, 256, size // 2, dtype=np.uint8).tobytes() + bytes(
        size - size // 2)


PART_LAYOUTS = {
    "one": lambda d: [d],
    "split": lambda d: [d[:7], d[7:100], d[100:]],
    "empty_parts": lambda d: [b"", d[:50], b"", memoryview(d)[50:], b""],
    "array_view": lambda d: [memoryview(np.frombuffer(d, np.uint8))],
    "many": lambda d: [d[i:i + 997] for i in range(0, len(d), 997)],
}


@pytest.mark.parametrize("layout", sorted(PART_LAYOUTS))
@pytest.mark.parametrize("codec", CODECS)
def test_put_parts_is_put_of_the_joined_bytes(tmp_path, codec, layout):
    """Same digest and same content as ``put`` of the joined bytes, under
    every codec, on both sides of the raw cut-off at 64 bytes."""
    for size in (0, 10, 64, 65, 10_000):
        data = _content(size)
        parts = PART_LAYOUTS[layout](data)
        by_parts = ObjectStore(tmp_path / f"p{size}", codec=codec)
        joined = ObjectStore(tmp_path / f"j{size}", codec=codec)
        digest = by_parts.put_parts(parts)
        assert digest == joined.put(data) == sha256_hex(data)
        assert by_parts.get(digest) == data
        payload = by_parts.get_encoded(digest)
        assert store_mod.decode_frame(payload) == data
        if codec == "zstd" and size > 64:
            # the store's one-shot decompress needs the size in the frame
            frame = store_mod.zstd.get_frame_parameters(payload[5:])
            assert frame.content_size == size


@pytest.mark.parametrize("first", ["put", "put_parts"])
def test_put_parts_and_put_dedup_each_other(tmp_path, first):
    from repro import tracing

    store = ObjectStore(tmp_path)
    data = _content(5000)
    ops = {"put": lambda: store.put(data),
           "put_parts": lambda: store.put_parts(PART_LAYOUTS["split"](data))}
    second = "put_parts" if first == "put" else "put"
    digest = ops[first]()
    assert ops[second]() == digest
    hashed = [s for s in tracing.spans() if s.name == "store.hash"][-1]
    assert hashed.attrs == {"bytes": len(data), "dedup": True}
    assert list(store.iter_objects()) == [digest]
    assert store.get(digest) == data


@pytest.mark.parametrize("backend", ["loopback", "http", "s3", "tiered"])
def test_put_parts_same_digest_on_every_backend(tmp_path, backend):
    """Backends without a streaming path join the parts: the digest and
    the stored content are the local store's."""
    from repro.core import (LoopbackTransport, RemoteServer, RemoteStore,
                            TieredStore, connect, serve_http, serve_s3)

    far = ObjectStore(tmp_path / "far")
    httpd = None
    if backend == "http":
        httpd, url = serve_http(far)
        store = connect(url)
    elif backend == "s3":
        httpd, url = serve_s3(tmp_path / "far")
        store = connect(url)
    else:
        store = RemoteStore(LoopbackTransport(RemoteServer(far)))
        if backend == "tiered":
            store = TieredStore(ObjectStore(tmp_path / "near"), store)
            far = store.local  # writes land in the local tier
    try:
        data = _content(20_000)
        parts = PART_LAYOUTS["many"](data)
        digest = store.put_parts(parts)
        assert digest == ObjectStore(tmp_path / "local").put_parts(parts)
        assert digest == sha256_hex(data)
        assert store.get(digest) == data
        assert far.get(digest) == data
        assert store.put(data) == digest
    finally:
        if backend in ("http", "s3"):
            store.close()
        if httpd is not None:
            httpd.shutdown()


# ------------------------------------------- zstd's multi-threaded mode
needs_zstd = pytest.mark.skipif(store_mod.zstd is None,
                                reason="zstandard is not installed")
#: one and a half times the multi-threaded cut-off: a frame of several jobs
LARGE = store_mod.ZSTD_MT_MIN_BYTES * 3 // 2 + 12
SMALL = 1 << 20  # where single- and multi-threaded frames already differ


def _floats(n):
    """``n`` bytes of float32 noise, like a checkpoint's state."""
    rng = np.random.default_rng(n)
    return rng.standard_normal(n // 4, dtype=np.float32).tobytes()


@pytest.fixture(scope="module")
def large():
    return _floats(LARGE)


def _frame(data, threads):
    """The zstd payload of ``data`` made by libzstd directly, streamed; a
    multi-threaded frame flushes its jobs before its end."""
    zstd = store_mod.zstd
    c = zstd.ZstdCompressor(level=3, threads=threads).compressobj(
        size=len(data))
    out = b"RPR1\x01" + c.compress(data)
    if threads:
        out += c.flush(zstd.COMPRESSOBJ_FLUSH_BLOCK)
    return out + c.flush()


def _parts(data):
    """Parts of uneven sizes, as a tensorfile's column buffers come."""
    cuts = [0, 3, 5 << 20, (5 << 20) + 1, 17 << 20, len(data)]
    return [memoryview(data)[a:b] for a, b in zip(cuts, cuts[1:])]


def _compress_span():
    from repro import tracing

    return [s for s in tracing.spans() if s.name == "store.compress"][-1]


@needs_zstd
@pytest.mark.parametrize("via", ["put_parts", "put_encoded"])
def test_large_object_roundtrips(tmp_path, large, via):
    """Above the cut-off the object reads back through ``get``, through
    any zstd decoder, and after a copy of its payload to another store."""
    store = ObjectStore(tmp_path / "a")
    digest = store.put_parts(_parts(large))
    assert digest == sha256_hex(large)
    payload = store.get_encoded(digest)
    frame = store_mod.zstd.get_frame_parameters(payload[5:])
    assert frame.content_size == LARGE
    assert store_mod.zstd.ZstdDecompressor().decompress(payload[5:]) == large
    if via == "put_encoded":
        store = ObjectStore(tmp_path / "b")
        assert store.put_encoded(payload) == digest
        assert store.get_encoded(digest) == payload
    assert store.get(digest) == large


@needs_zstd
@pytest.mark.parametrize("cores", [1, 2, 4])
@pytest.mark.parametrize("size", ["odd", "whole_jobs"])
def test_large_payload_is_the_same_on_any_core_count(tmp_path, large,
                                                     monkeypatch, cores,
                                                     size):
    """The frame is libzstd's multi-threaded one, byte for byte the same
    whatever number of workers made it; the span names that number.
    ``whole_jobs`` ends on a full libzstd job (8 MiB at level 3)."""
    data = large if size == "odd" else _floats(2 * store_mod.ZSTD_MT_MIN_BYTES)
    monkeypatch.setattr(store_mod, "usable_cores", lambda: cores)
    store = ObjectStore(tmp_path)
    digest = store.put_parts(_parts(data))
    assert _compress_span().attrs["workers"] == cores
    assert store.get_encoded(digest) == _frame(data, threads=1)


@needs_zstd
def test_workers_capped(monkeypatch):
    monkeypatch.setattr(store_mod, "usable_cores", lambda: 1000)
    assert store_mod.zstd_workers(LARGE) == store_mod.ZSTD_MT_MAX_WORKERS
    assert store_mod.zstd_workers(store_mod.ZSTD_MT_MIN_BYTES - 1) == 0


@needs_zstd
def test_small_object_is_the_single_threaded_frame(tmp_path):
    data = _floats(SMALL)
    digest = ObjectStore(tmp_path).put(data)
    assert _frame(data, threads=0) != _frame(data, threads=1)
    assert ObjectStore(tmp_path).get_encoded(digest) == _frame(data, 0)


@needs_zstd
def test_large_single_threaded_frame_still_reads(tmp_path, large):
    """A large object stored single-threaded, as before the cut-off
    existed, reads back and keeps its payload."""
    old = _frame(large, threads=0)
    store = ObjectStore(tmp_path)
    digest = store.put_encoded(old)
    assert store.get(digest) == large
    assert store.get_encoded(digest) == old
    assert store.put(large) == digest  # dedup: the old payload stays
    assert store.get_encoded(digest) == old


@needs_zstd
def test_threads_put_large_objects_at_once(tmp_path, large):
    """Each writing thread compresses with a context of its own."""
    import threading

    store = ObjectStore(tmp_path)
    blobs = [large, large[::-1]]
    barrier = threading.Barrier(len(blobs))
    digests = [None] * len(blobs)

    def worker(i):
        barrier.wait()
        digests[i] = store.put_parts(_parts(blobs[i]))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(blobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for digest, blob in zip(digests, blobs):
        assert digest == sha256_hex(blob)
        assert store.get(digest) == blob
        assert store.get_encoded(digest) == _frame(blob, threads=1)


@needs_zstd
@pytest.mark.parametrize("writer", ["encode_frame", "s3"])
@pytest.mark.parametrize("size", ["small", "large"])
def test_every_writer_stores_the_same_payload(tmp_path, large, writer, size):
    """A payload's bytes depend on its content alone, whatever backend
    wrote it."""
    from repro.core import connect, serve_s3

    data = large if size == "large" else _floats(SMALL)
    digest = ObjectStore(tmp_path / "fs").put(data)
    want = ObjectStore(tmp_path / "fs").get_encoded(digest)
    if writer == "encode_frame":
        assert store_mod.encode_frame(data) == want
        return
    httpd, url = serve_s3(tmp_path / "bucket")
    try:
        s3 = connect(url)
        assert s3.put(data) == digest
        assert s3.get_encoded(digest) == want
        s3.close()
    finally:
        httpd.shutdown()


@pytest.mark.parametrize("codec,size,threaded", [
    pytest.param("zstd", "large", True, marks=needs_zstd),
    pytest.param("zstd", "small", False, marks=needs_zstd),
    ("zlib", "large", False),
    ("raw", "large", False),
])
def test_compress_span_counts_workers(tmp_path, large, codec, size,
                                      threaded):
    """``workers`` is libzstd's worker count, 0 where no worker ran."""
    data = large if size == "large" else _floats(SMALL)
    ObjectStore(tmp_path, codec=codec).put(data)
    workers = _compress_span().attrs["workers"]
    assert workers >= 1 if threaded else workers == 0


# ------------------------------------------------------------ ref namespaces
def test_namespaced_refs_roundtrip(tmp_path):
    store = ObjectStore(tmp_path)
    store.set_ref("cache/ab/cdef", "d1")
    store.set_ref("cache/ab/ffff", "d2")
    store.set_ref("branch=main", "d3")
    assert store.get_ref("cache/ab/cdef") == "d1"
    assert list(store.iter_refs("cache/")) == ["cache/ab/cdef",
                                               "cache/ab/ffff"]
    assert "branch=main" in list(store.iter_refs())
    store.delete_ref("cache/ab/cdef")
    with pytest.raises(RefNotFound):
        store.get_ref("cache/ab/cdef")


@pytest.mark.parametrize("bad", ["", ".", "..", "a/../b", "a//b", "/a",
                                 ".hidden", "ns/.hidden"])
def test_bad_ref_names_rejected(tmp_path, bad):
    store = ObjectStore(tmp_path)
    with pytest.raises(ValueError):
        store.set_ref(bad, "x")


def test_cas_ref_atomic_under_threads(tmp_path):
    """N threads × K increments with CAS-retry: no lost updates."""
    import threading

    store = ObjectStore(tmp_path)
    store.set_ref("ctr", "0")
    n_threads, n_incr = 8, 25
    conflicts = [0] * n_threads

    def worker(tid):
        for _ in range(n_incr):
            while True:
                cur = store.get_ref("ctr")
                try:
                    store.cas_ref("ctr", cur, str(int(cur) + 1))
                    break
                except RefConflict:
                    conflicts[tid] += 1

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert store.get_ref("ctr") == str(n_threads * n_incr)


def test_concurrent_puts_single_object(tmp_path):
    """Racing put()s of the same content agree on one durable object."""
    import threading

    store = ObjectStore(tmp_path)
    data = b"contended blob" * 512
    digests = []
    lock = threading.Lock()

    def worker():
        d = store.put(data)
        with lock:
            digests.append(d)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert set(digests) == {sha256_hex(data)}
    assert list(store.iter_objects()) == [sha256_hex(data)]
    assert store.get(sha256_hex(data)) == data


@settings(max_examples=50, deadline=None)
@given(st.binary(min_size=0, max_size=4096))
def test_property_content_addressing(tmp_path_factory, data):
    """Same bytes → same digest; get(put(x)) == x."""
    store = ObjectStore(tmp_path_factory.mktemp("s"))
    digest = store.put(data)
    assert store.get(digest) == data
    assert store.put(data) == digest


# ---------------------------------------------------------------- tensorfile
DTYPES = [np.float32, np.float64, np.int32, np.int64, np.uint8, np.bool_]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("row_shape", [(), (3,), (2, 4)])
def test_tensorfile_roundtrip_dtypes(dtype, row_shape):
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 2, size=(17, *row_shape)).astype(dtype)
    blob, meta = tf.encode({"a": arr, "b": np.arange(17)})
    out = tf.decode(blob)
    np.testing.assert_array_equal(out["a"], arr)
    assert meta["nrows"] == 17


def test_tensorfile_bfloat16_roundtrip():
    import ml_dtypes

    arr = np.arange(32, dtype=np.float32).astype(ml_dtypes.bfloat16)
    blob, _ = tf.encode({"a": arr.reshape(8, 4)})
    out = tf.decode(blob)
    np.testing.assert_array_equal(out["a"], arr.reshape(8, 4))


def test_tensorfile_ragged_rejected():
    with pytest.raises(SchemaError):
        tf.encode({"a": np.zeros(3), "b": np.zeros(4)})


def test_tensorfile_stats():
    blob, meta = tf.encode({"a": np.array([1.0, np.nan, 3.0], np.float32)})
    st_ = meta["stats"]["a"]
    assert st_["nan_count"] == 1
    assert st_["min"] == 1.0 and st_["max"] == 3.0


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 64),
    cols=st.lists(st.sampled_from("abcdef"), min_size=1, max_size=4,
                  unique=True),
    dtype=st.sampled_from(DTYPES),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_tensorfile_roundtrip(n, cols, dtype, seed):
    """encode∘decode is the identity, and the digest is deterministic."""
    rng = np.random.default_rng(seed)
    data = {c: rng.integers(-5, 5, size=(n, 2)).astype(dtype) for c in cols}
    blob1, _ = tf.encode(data)
    blob2, _ = tf.encode(data)
    assert sha256_hex(blob1) == sha256_hex(blob2)  # deterministic encode
    out = tf.decode(blob1)
    for c in cols:
        np.testing.assert_array_equal(out[c], data[c])


def test_schema_project_and_compat():
    s = tf.Schema.of({"a": np.zeros((2, 3)), "b": np.zeros(2)})
    assert s.names() == ["a", "b"]
    p = s.project(["a"])
    assert p.names() == ["a"]
    with pytest.raises(SchemaError):
        s.check_compatible(p)
