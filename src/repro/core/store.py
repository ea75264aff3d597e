"""Content-addressed object store with S3-like key layout.

This is the "S3" of the paper (Table 1, Fig. 2/3): every artifact — tensor
files, table snapshots, commits, run manifests — is an immutable blob keyed by
the sha-256 of its *uncompressed* content.  Immutability + content addressing
is what makes branches copy-on-write and runs replayable.

The filesystem backend mirrors an S3 key scheme (``objects/ab/cdef...``) so a
real S3/GCS backend is a drop-in replacement of this one class.

Compression is pluggable per-blob via a codec byte in the framing, so a store
written with zstd stays readable on a host that only has the stdlib: zstd is
preferred when the ``zstandard`` package is importable, with a zlib fallback
otherwise (distinct codec byte — old blobs keep decoding either way).

Refs come in two layouts:

    flat:        ``branch=main``, ``tag=v1.0``, ``runs-head``
    namespaced:  ``cache/ab/cdef...`` — "/"-separated segments map to
                 subdirectories, used by the run cache so its (potentially
                 many) entries shard like objects do
"""

from __future__ import annotations

import hashlib
import os
import tempfile
import threading
import zlib
from contextlib import contextmanager
from pathlib import Path
from typing import (Dict, Iterable, Iterator, List, Optional, Protocol,
                    Sequence, Set, Tuple, runtime_checkable)

try:  # optional: preferred codec when available
    import zstandard as zstd
except ImportError:  # pragma: no cover - exercised on the no-zstd CI leg
    zstd = None

try:  # POSIX file locking for cross-process CAS; absent on Windows
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX fallback: thread lock only
    fcntl = None

from .. import tracing
from .errors import (AmbiguousRefUpdate, CodecUnavailable, ObjectNotFound,
                     RefConflict, RefNotFound)

_MAGIC = b"RPR1"  # blob framing: magic + 1 byte codec id
_CODEC_RAW = b"\x00"
_CODEC_ZSTD = b"\x01"
_CODEC_ZLIB = b"\x02"

#: GC generation token, stored in the refs keyspace so every backend can
#: CAS it.  A sweep bumps it (monotone integer, as text) BEFORE marking;
#: an in-flight push/pull captures it at transfer start and validates it
#: inside its final ``cas_refs`` batch — a push that raced a sweep fails
#: the ref update cleanly and re-uploads instead of publishing refs to
#: deleted blobs (docs/remote_store.md, "Concurrent-safe remote GC").
GC_GENERATION_REF = "gc/generation"

#: codecs this build can *write* ("auto" = best available compressor)
WRITE_CODECS = ("auto", "raw", "zlib") + (("zstd",) if zstd else ())


def sha256_hex(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def frame_raw(data: bytes) -> bytes:
    """Frame ``data`` uncompressed (magic + raw codec byte).  The shape a
    backend without a compressor hands out for encoded transfers."""
    return _MAGIC + _CODEC_RAW + data


#: content of this many bytes or more is compressed by libzstd's
#: multi-threaded mode: still one standard frame, and the same bytes for
#: any number of workers, so only the speed depends on the host's cores.
#: On a TPU v5e host it is as fast as one thread at 16 MiB of float32
#: state and twice as fast at 32 MiB (PERF.md, section 6)
ZSTD_MT_MIN_BYTES = 16 << 20
#: the most libzstd workers one compression uses: on that host 2 to 13
#: workers compress a 4.4-GB checkpoint at 825-863 MB/s, one at 374
ZSTD_MT_MAX_WORKERS = 4
#: the most content one call into a compressor takes: a call returns its
#: output as one buffer that grows by copying, so a column of a gigabyte
#: fed whole costs more to collect than to compress
_FEED_BYTES = 4 << 20


def usable_cores() -> int:
    """CPU cores this process may run on."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - not on Linux
        return os.cpu_count() or 1


def zstd_workers(n: int) -> int:
    """libzstd workers for ``n`` bytes of content: 0 (single-threaded)
    below ``ZSTD_MT_MIN_BYTES``, else the usable cores up to
    ``ZSTD_MT_MAX_WORKERS``."""
    if n < ZSTD_MT_MIN_BYTES:
        return 0
    return max(1, min(usable_cores(), ZSTD_MT_MAX_WORKERS))


def _feed(c, parts: Sequence) -> Iterator[bytes]:
    """``c.compress`` over ``parts`` in slices of at most ``_FEED_BYTES``
    (the codec's output does not depend on how its input is cut)."""
    for p in parts:
        m = memoryview(p).cast("B")
        for i in range(0, m.nbytes, _FEED_BYTES):
            yield c.compress(m[i:i + _FEED_BYTES])


def _encode_parts(parts: Sequence, n: int, *, codec: str, level: int,
                  compressors: Optional[Dict[int, object]] = None
                  ) -> Tuple[List, int]:
    """The at-rest payload of content ``parts`` (``n`` bytes in all), as
    a list of buffers — the frame header, then the parts themselves (raw)
    or the codec's streamed output — and the libzstd workers that made it.

    Every writer frames through here, so a payload's bytes depend on its
    content, codec and level alone.  ``compressors`` is the calling
    thread's zstd compressors by worker count, reused across calls."""
    if n <= 64 or codec == "raw":
        return [_MAGIC + _CODEC_RAW, *parts], 0
    if codec != "zstd":
        # zstd levels reach 22 but zlib's cap is 9 — clamp so a store
        # tuned for zstd keeps working on a host that falls back to zlib
        c = zlib.compressobj(min(level, 9))
        return [_MAGIC + _CODEC_ZLIB, *_feed(c, parts), c.flush()], 0
    workers = zstd_workers(n)
    z = None if compressors is None else compressors.get(workers)
    if z is None:
        z = zstd.ZstdCompressor(level=level, threads=workers)
        if compressors is not None:
            compressors[workers] = z
    # the frame records the content size: a one-shot decompress needs it
    c = z.compressobj(size=n)
    out = [_MAGIC + _CODEC_ZSTD, *_feed(c, parts)]
    if workers:
        # libzstd's workers may or may not have taken a last, exactly full
        # job before the frame's end is asked for, and the frame's end
        # differs by a block between the two: flushing the jobs first
        # makes every frame end with an empty last block
        out.append(c.flush(zstd.COMPRESSOBJ_FLUSH_BLOCK))
    out.append(c.flush())
    return out, workers


def encode_frame(data: bytes, *, codec: str = "auto", level: int = 3) -> bytes:
    """Frame (and compress) raw content bytes the way the store does at
    rest — the encoder counterpart of :func:`decode_frame`, for backends
    that keep blobs in framed form off-disk (the S3 keyspace)."""
    if codec == "auto":
        codec = "zstd" if zstd is not None else "zlib"
    if codec == "zstd" and zstd is None:
        raise ValueError("codec='zstd' but zstandard is not installed")
    payload, _ = _encode_parts((data,), len(data), codec=codec, level=level)
    return b"".join(payload)


def decode_frame(payload: bytes, *, what: str = "object") -> bytes:
    """Decode one framed blob payload back to its raw content bytes.

    The inverse of the store's at-rest framing, shared by every consumer of
    *encoded* blobs (the on-disk payloads, compressed wire frames, the S3
    keyspace): magic check, codec dispatch, decompress.  Raises
    :class:`CodecUnavailable` when the payload needs a compressor this host
    does not have (zstd payload, no zstandard package) so transfer paths
    can fall back to raw blobs instead of failing the whole operation."""
    if payload[:4] != _MAGIC:
        raise ObjectNotFound(f"corrupt {what}: bad frame magic")
    codec, body = payload[4:5], payload[5:]
    if codec == _CODEC_RAW:
        return body
    if codec == _CODEC_ZLIB:
        return zlib.decompress(body)
    if codec == _CODEC_ZSTD:
        if zstd is None:
            raise CodecUnavailable(
                f"{what} is zstd-compressed but the zstandard package "
                "is not installed")
        return zstd.ZstdDecompressor().decompress(body)
    raise ObjectNotFound(f"unknown codec {codec!r} for {what}")


@runtime_checkable
class StoreBackend(Protocol):
    """The object-store wire contract every backend speaks.

    Extracted from the filesystem :class:`ObjectStore` so that a remote
    backend (:class:`repro.core.remote.RemoteStore`), a tiered composite
    (:class:`repro.core.remote.TieredStore`), or a real S3/GCS client is a
    drop-in replacement anywhere a store is accepted (catalog, run cache,
    ledger, table IO, sync).  Semantics every implementation must honor:

    * **objects** are immutable and content addressed — ``put`` is
      idempotent, ``get`` verifies the digest, partially written objects are
      never observable;
    * **refs** are tiny mutable pointers with atomic ``cas_ref``
      (linearizable per ref name) and all-or-nothing ``cas_refs`` across
      several names (the multi-ref push contract);
    * **listing** is paged and sorted so closure transfers can resume;
    * **exists** checks batch (``has_many``) and blob reads/writes batch
      (``get_many``/``put_many``) so transfers can dedup and pipeline
      without a round-trip per object.

    Backends may additionally implement the **optional delta capability**
    — ``has_chunks(hashes) -> Set[str]`` and
    ``put_objects_delta(items) -> (stored, stale)`` over content-defined
    chunk recipes (see :mod:`repro.core.delta`).  The sync engine probes
    for these with ``hasattr`` and degrades to whole-frame transfer when
    absent, so the methods are deliberately NOT part of this protocol:
    implementing them is a bandwidth optimization, never a correctness
    requirement.
    """

    # objects -----------------------------------------------------------
    def put(self, data: bytes) -> str: ...
    # ``put`` of ``b"".join(parts)`` (same digest, same stored content),
    # for a caller whose content is already a sequence of buffers: the
    # filesystem store hashes, compresses and writes them without joining
    def put_parts(self, parts: Sequence[bytes]) -> str: ...
    def get(self, digest: str) -> bytes: ...
    def has(self, digest: str) -> bool: ...
    def has_many(self, digests: Iterable[str]) -> Set[str]: ...
    def get_many(self, digests: Sequence[str]) -> Dict[str, bytes]: ...
    def put_many(self, blobs: Sequence[bytes]) -> List[str]: ...
    def size(self, digest: str) -> int: ...
    # upload age (seconds-since-epoch mtime of the stored payload): what
    # the GC grace window compares against — fs via stat, S3 via the
    # Last-Modified header, the wire via the stat_object op
    def mtime(self, digest: str) -> float: ...
    # combined (size, mtime) in ONE backend round-trip — what the sweep
    # uses per candidate so a remote collection never pays two
    def stat(self, digest: str) -> Tuple[int, float]: ...
    # best-effort mtime refresh of already-present objects (the sync
    # engine's touch-on-dedup): returns how many were actually touched —
    # 0 is a valid answer for backends with no cheap touch (S3), the GC
    # generation-retry path still protects those
    def touch_many(self, digests: Sequence[str]) -> int: ...
    def delete_object(self, digest: str) -> bool: ...
    # encoded (framed, possibly compressed) payload transfer: a blob
    # compressed once at rest crosses every hop in that form — see
    # ``decode_frame`` for the framing and docs/remote_store.md for the
    # wire-frame compression contract
    def get_encoded(self, digest: str) -> bytes: ...
    def put_encoded(self, payload: bytes) -> str: ...
    def get_many_encoded(self, digests: Sequence[str]) -> Dict[str, bytes]: ...
    # ``digests`` is an optional hint from a caller that already decoded
    # and digest-verified the payloads (the transfer engine does, for
    # accounting): backends whose far side re-verifies anyway may use it
    # to skip a redundant local decode
    def put_many_encoded(self, payloads: Sequence[bytes],
                         digests: Optional[Sequence[str]] = None
                         ) -> List[str]: ...
    def iter_objects(self) -> Iterator[str]: ...
    def list_objects(self, *, page_token: Optional[str] = None,
                     limit: int = 1000
                     ) -> Tuple[List[str], Optional[str]]: ...

    # refs --------------------------------------------------------------
    def set_ref(self, name: str, digest: str) -> None: ...
    def get_ref(self, name: str) -> str: ...
    def cas_ref(self, name: str, expected: Optional[str],
                new: str) -> None: ...
    def cas_refs(self, updates: Sequence[Tuple[str, Optional[str], str]]
                 ) -> None: ...
    def delete_ref(self, name: str) -> None: ...
    def iter_refs(self, prefix: str = "") -> Iterator[str]: ...
    def list_refs(self, prefix: str = "", *,
                  page_token: Optional[str] = None, limit: int = 1000
                  ) -> Tuple[List[Tuple[str, str]], Optional[str]]: ...


def read_generation(store: "StoreBackend") -> Optional[str]:
    """Current GC generation token of ``store`` (None = no sweep ever ran
    and nobody materialized the ref yet)."""
    try:
        return store.get_ref(GC_GENERATION_REF)
    except RefNotFound:
        return None


def ensure_generation(store: "StoreBackend") -> str:
    """Read the generation token, materializing ``"0"`` if absent — so a
    sync can always include an exact-value guard in its ``cas_refs`` batch
    (guarding on "absent" would make two concurrent first pushes conflict
    with each other instead of only with sweeps).  An ambiguous wire CAS
    (the materializing write may or may not have landed) resolves itself
    through the re-read at the top of the next attempt."""
    last: Optional[Exception] = None
    for _ in range(4):
        current = read_generation(store)
        if current is not None:
            return current
        try:
            store.cas_ref(GC_GENERATION_REF, None, "0")
            return "0"
        except (RefConflict, AmbiguousRefUpdate) as e:
            last = e  # racer / unknown delivery — the re-read decides
    raise RefConflict(
        f"could not materialize {GC_GENERATION_REF!r}") from last


def read_ref_or_none(store: "StoreBackend", name: str) -> Optional[str]:
    """``get_ref`` with the miss folded into the value (None = absent) —
    the read half of every CAS loop over coordination refs (GC generation,
    executor leases)."""
    try:
        return store.get_ref(name)
    except RefNotFound:
        return None


def try_cas_ref(store: "StoreBackend", name: str, expected: Optional[str],
                new: str) -> bool:
    """One CAS attempt as a boolean: True iff ``name`` moved from
    ``expected`` to ``new``.

    The primitive the executor's lease machinery is built on (claim,
    heartbeat, complete are all single-ref CAS transitions, exactly like
    the GC generation token): a clean :class:`RefConflict` is a lost race
    (False, the caller re-reads), and an :class:`AmbiguousRefUpdate` —
    a transport fault after the request may have been delivered — is
    resolved by re-reading: lease values embed owner + deadline, so
    observing our exact value means our write landed."""
    try:
        store.cas_ref(name, expected, new)
        return True
    except RefConflict:
        return False
    except AmbiguousRefUpdate:
        return read_ref_or_none(store, name) == new


def bump_generation(store: "StoreBackend") -> str:
    """Advance the GC generation token (CAS loop, any backend).  Called at
    sweep START, before the mark phase reads refs: any sync that captured
    the previous token — i.e. any sync whose uploads could predate the
    mark — fails its ref update cleanly and retries, instead of publishing
    refs to objects the sweep is about to delete."""
    last: Optional[Exception] = None
    for _ in range(16):
        current = read_generation(store)
        nxt = str(int(current) + 1) if current is not None else "1"
        try:
            store.cas_ref(GC_GENERATION_REF, current, nxt)
            return nxt
        except RefConflict as e:
            last = e  # concurrent bump/materialize — re-read and retry
        except AmbiguousRefUpdate as e:
            # the bump may have landed before the fault: a re-read showing
            # our exact value claims it (any OTHER change restarts — some
            # concurrent bump won, and a sweep must own a fresh token)
            if read_generation(store) == nxt:
                return nxt
            last = e
    raise RefConflict(
        f"could not advance {GC_GENERATION_REF!r} "
        "(persistent contention or transport faults)") from last


class ObjectStore:
    """Immutable content-addressed blobs + mutable atomic refs.

    Objects:  ``put(bytes) -> digest``; ``get(digest) -> bytes``.
    Refs:     ``set_ref/get_ref/cas_ref`` — tiny mutable pointers used only by
              the catalog for branch heads and the run cache for cache keys
              (everything else is immutable).
    """

    def __init__(self, root: str | os.PathLike, *, compress: bool = True,
                 level: int = 3, codec: str = "auto"):
        self.root = Path(root)
        self.obj_dir = self.root / "objects"
        self.ref_dir = self.root / "refs"
        self.obj_dir.mkdir(parents=True, exist_ok=True)
        self.ref_dir.mkdir(parents=True, exist_ok=True)
        self.compress = compress
        if codec not in ("auto", "raw", "zlib", "zstd"):
            raise ValueError(f"unknown codec {codec!r}")
        if codec == "zstd" and zstd is None:
            raise ValueError("codec='zstd' but zstandard is not installed")
        if codec == "auto":
            codec = "zstd" if zstd is not None else "zlib"
        self.codec = codec
        self.level = level
        # a zstd context must not serve two threads at once (concurrent use
        # corrupts frames or crashes): each thread reuses its own
        self._zstd_ctx = threading.local()
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ blobs
    def _path(self, digest: str) -> Path:
        return self.obj_dir / digest[:2] / digest[2:]

    def _compressors(self) -> Dict[int, object]:
        """This thread's zstd compressors by worker count."""
        ctx = getattr(self._zstd_ctx, "compressors", None)
        if ctx is None:
            ctx = self._zstd_ctx.compressors = {}
        return ctx

    def _decompressor(self):
        """This thread's zstd decompressor."""
        ctx = getattr(self._zstd_ctx, "decompressor", None)
        if ctx is None:
            ctx = self._zstd_ctx.decompressor = zstd.ZstdDecompressor()
        return ctx

    def _decode(self, digest: str, payload: bytes) -> bytes:
        if payload[4:5] == _CODEC_ZSTD and zstd is not None:
            # hot path: reuse this thread's decompressor across reads
            if payload[:4] != _MAGIC:
                raise ObjectNotFound(f"corrupt object {digest}")
            return self._decompressor().decompress(payload[5:])
        return decode_frame(payload, what=f"object {digest}")

    def put(self, data: bytes) -> str:
        return self.put_parts((data,))

    def put_parts(self, parts: Sequence) -> str:
        """Store the content ``b"".join(parts)`` without joining it: the
        parts are hashed, compressed and written in turn.  The digest and
        the stored content are those of ``put`` of the joined bytes."""
        n = sum(memoryview(p).nbytes for p in parts)
        with tracing.span("store.hash", bytes=n) as s:
            h = hashlib.sha256()
            for p in parts:
                h.update(p)
            digest = h.hexdigest()
            path = self._path(digest)
            # dedup: content addressing makes re-puts free
            dedup = path.exists()
            s.set(dedup=dedup)
        if dedup:
            return digest
        with tracing.span("store.compress", bytes=n) as s:
            payload, workers = _encode_parts(
                parts, n, codec=self.codec if self.compress else "raw",
                level=self.level, compressors=self._compressors())
            size = sum(memoryview(p).nbytes for p in payload)
            s.set(bytes_out=size, workers=workers)
        with tracing.span("store.write", bytes=size):
            path.parent.mkdir(parents=True, exist_ok=True)
            # Write-then-rename so readers never observe partial objects.
            fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=".tmp-")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.writelines(payload)
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        return digest

    def get(self, digest: str) -> bytes:
        path = self._path(digest)
        try:
            payload = path.read_bytes()
        except FileNotFoundError:
            raise ObjectNotFound(digest) from None
        data = self._decode(digest, payload)
        if sha256_hex(data) != digest:
            raise ObjectNotFound(f"digest mismatch for {digest}")
        return data

    def has(self, digest: str) -> bool:
        return self._path(digest).exists()

    def has_many(self, digests: Iterable[str]) -> Set[str]:
        """Subset of ``digests`` present in the store (batched exists —
        one call per transfer chunk instead of one round-trip per object)."""
        return {d for d in digests if self.has(d)}

    def get_many(self, digests: Sequence[str]) -> Dict[str, bytes]:
        """Batched read.  Local disk gains nothing from batching, but the
        remote backends do — this keeps the wire contract uniform so the
        transfer engine can pipeline leaf blobs in chunks everywhere."""
        return {d: self.get(d) for d in digests}

    def put_many(self, blobs: Sequence[bytes]) -> List[str]:
        """Batched write, digests returned in input order."""
        return [self.put(b) for b in blobs]

    def delete_object(self, digest: str) -> bool:
        """Remove one object (GC sweep).  Idempotent: missing → False."""
        try:
            self._path(digest).unlink()
            return True
        except FileNotFoundError:
            return False

    def touch_many(self, digests: Sequence[str]) -> int:
        """Reset present objects' mtimes to now; returns how many existed.

        The sync engine calls this on dedup hits so a long push can't have
        its already-present objects age past the GC grace window while the
        rest of the closure is still uploading (the ref flip that would
        protect them only lands at the end)."""
        touched = 0
        for digest in digests:
            try:
                os.utime(self._path(digest))
                touched += 1
            except FileNotFoundError:
                continue  # raced a sweep: the generation token catches it
        return touched

    # ------------------------------------------------- encoded payloads
    def get_encoded(self, digest: str) -> bytes:
        """The object's framed at-rest payload, compression and all.

        What compressed wire frames carry: a blob pays for compression
        once (at ``put``) and crosses every subsequent hop in that form.
        The receiver (:meth:`put_encoded`) decodes and digest-verifies, so
        handing out the payload un-reverified is safe."""
        try:
            payload = self._path(digest).read_bytes()
        except FileNotFoundError:
            raise ObjectNotFound(digest) from None
        if payload[:4] != _MAGIC:
            raise ObjectNotFound(f"corrupt object {digest}")
        return payload

    def put_encoded(self, payload: bytes) -> str:
        """Store a framed payload as-is (no recompression): decode to
        verify the content digest, then land the original payload under
        it.  Raises :class:`~repro.core.errors.CodecUnavailable` when the
        payload's codec cannot be decoded here — callers fall back to raw
        transfer (the sender re-sends uncompressed)."""
        data = decode_frame(payload, what="encoded payload")
        digest = sha256_hex(data)
        path = self._path(digest)
        if path.exists():
            return digest
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=".tmp-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(payload)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return digest

    def get_many_encoded(self, digests: Sequence[str]) -> Dict[str, bytes]:
        return {d: self.get_encoded(d) for d in digests}

    def put_many_encoded(self, payloads: Sequence[bytes],
                         digests: Optional[Sequence[str]] = None
                         ) -> List[str]:
        # the digest hint is ignored here: this store is where the payload
        # comes to rest, so it always decodes and verifies for itself
        return [self.put_encoded(p) for p in payloads]

    def size(self, digest: str) -> int:
        """On-disk (compressed) size — used by benchmarks."""
        try:
            return self._path(digest).stat().st_size
        except FileNotFoundError:
            raise ObjectNotFound(digest) from None

    def mtime(self, digest: str) -> float:
        """When the object landed here (write-then-rename publish time).
        The GC grace window keys off this: a sweep never deletes an object
        younger than ``prune_age``, so an in-flight push's uploads are
        safe even before its refs move."""
        return self.stat(digest)[1]

    def stat(self, digest: str) -> Tuple[int, float]:
        """``(on-disk size, mtime)`` from one os.stat."""
        try:
            st = self._path(digest).stat()
        except FileNotFoundError:
            raise ObjectNotFound(digest) from None
        return st.st_size, st.st_mtime

    def iter_objects(self) -> Iterator[str]:
        for sub in sorted(self.obj_dir.iterdir()):
            if not sub.is_dir():
                continue
            for obj in sorted(sub.iterdir()):
                if not obj.name.startswith("."):
                    yield sub.name + obj.name

    def list_objects(self, *, page_token: Optional[str] = None,
                     limit: int = 1000
                     ) -> Tuple[List[str], Optional[str]]:
        """One page of object digests in sorted order.

        ``page_token`` is the last digest of the previous page (exclusive
        resume point — the same shape as S3 ListObjectsV2 continuation
        tokens over the ``objects/ab/cdef...`` key layout)."""
        limit = max(1, limit)
        page: List[str] = []
        for digest in self.iter_objects():
            if page_token is not None and digest <= page_token:
                continue
            page.append(digest)
            if len(page) >= limit:
                return page, digest
        return page, None

    # ------------------------------------------------------------------- refs
    def _ref_path(self, name: str) -> Path:
        parts = name.split("/")
        for part in parts:
            if not part or part.startswith("."):
                raise ValueError(f"bad ref name {name!r}")
        return self.ref_dir.joinpath(*parts)

    def set_ref(self, name: str, digest: str) -> None:
        path = self._ref_path(name)
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=".tmp-")
        with os.fdopen(fd, "w") as f:
            f.write(digest)
        os.replace(tmp, path)

    def get_ref(self, name: str) -> str:
        try:
            return self._ref_path(name).read_text().strip()
        except FileNotFoundError:
            raise RefNotFound(name) from None

    @contextmanager
    def ref_guard(self):
        """Exclusive critical section over this store's refs, across
        threads, instances AND processes (exclusive ``flock`` on a sidecar
        lock file).  ``cas_ref`` runs inside it; composites like
        ``TieredStore`` borrow it so their read-compare-write against a
        merged ref view stays linearizable too.  Not reentrant."""
        with self._lock, open(self.ref_dir / ".cas-lock", "w") as lockf:
            if fcntl is not None:
                fcntl.flock(lockf, fcntl.LOCK_EX)  # released on close
            yield

    def cas_ref(self, name: str, expected: Optional[str], new: str) -> None:
        """Compare-and-set a ref (atomicity of catalog commits).

        Linearizable across *instances and processes* sharing one store
        directory, not just threads of one instance — two servers fronting
        the same tree cannot both win a race (the contract ``RemoteStore``
        clients and push's ref handoff rely on)."""
        with self.ref_guard():
            current: Optional[str]
            try:
                current = self.get_ref(name)
            except RefNotFound:
                current = None
            if current != expected:
                raise RefConflict(
                    f"ref {name}: expected {expected!r}, found {current!r}")
            self.set_ref(name, new)

    def cas_refs(self, updates: Sequence[Tuple[str, Optional[str], str]]
                 ) -> None:
        """All-or-nothing compare-and-set across several refs.

        Every expectation is validated inside ONE ref-guard critical section
        before any ref moves, so a single stale expectation leaves every ref
        untouched — the atomicity contract a multi-ref push rides on (one
        conflicting branch rolls back the entire ref update).  Same
        cross-thread/-instance/-process linearizability as ``cas_ref``."""
        with self.ref_guard():
            for name, expected, _new in updates:
                try:
                    current: Optional[str] = self.get_ref(name)
                except RefNotFound:
                    current = None
                if current != expected:
                    raise RefConflict(
                        f"ref {name}: expected {expected!r}, found "
                        f"{current!r} (no ref in this batch was updated)")
            for name, _expected, new in updates:
                self.set_ref(name, new)

    def delete_ref(self, name: str) -> None:
        try:
            self._ref_path(name).unlink()
        except FileNotFoundError:
            raise RefNotFound(name) from None

    def iter_refs(self, prefix: str = "") -> Iterator[str]:
        """All ref names (namespaced refs as ``ns/sub/name``), sorted."""
        names = []
        for dirpath, dirnames, filenames in os.walk(self.ref_dir):
            dirnames[:] = sorted(d for d in dirnames if not d.startswith("."))
            rel = Path(dirpath).relative_to(self.ref_dir)
            for fn in filenames:
                if fn.startswith("."):
                    continue
                name = fn if rel == Path(".") else (rel / fn).as_posix()
                if name.startswith(prefix):
                    names.append(name)
        yield from sorted(names)

    def list_refs(self, prefix: str = "", *,
                  page_token: Optional[str] = None, limit: int = 1000
                  ) -> Tuple[List[Tuple[str, str]], Optional[str]]:
        """One page of ``(name, digest)`` pairs in sorted name order.

        Returning the value with the name saves the per-ref ``get_ref``
        round-trip a remote sync would otherwise pay.  Refs deleted between
        the directory walk and the read are skipped (no torn pages)."""
        limit = max(1, limit)
        page: List[Tuple[str, str]] = []
        last: Optional[str] = None
        for name in self.iter_refs(prefix):
            if page_token is not None and name <= page_token:
                continue
            try:
                page.append((name, self.get_ref(name)))
            except RefNotFound:  # concurrently deleted
                continue
            last = name
            if len(page) >= limit:
                return page, last
        return page, None
