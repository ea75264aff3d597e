"""S3-compatible object-store backend: the paper's claim made literal.

The filesystem :class:`~repro.core.store.ObjectStore` mirrors an S3 key
scheme precisely so a real object-store backend is a drop-in replacement —
this module is that replacement.  :class:`S3Backend` implements the full
:class:`~repro.core.store.StoreBackend` contract over an S3-style REST
dialect, so ``push``/``pull``/``clone``, the run-cache closure transfer,
tiered reads and remote-side GC all run against commodity object storage
with no catalog service in between:

    keyspace        ``<bucket>/objects/<d0d1>/<d2...>``  framed blob payloads
                    ``<bucket>/refs/<name>``             tiny digest pointers

    GET / HEAD / PUT / DELETE <key>        object + ref bytes
    GET ?list-type=2&prefix=&start-after=  ListObjectsV2-style paged listing
    PUT + If-Match / If-None-Match         conditional writes → ref CAS

Blobs are stored in the same framed (magic + codec byte) form the
filesystem store uses at rest, so an S3 bucket and a store directory are
byte-compatible mirrors of each other, and encoded wire transfers
(``get_encoded``/``put_encoded``) pass payloads straight through without
recompressing.

Ref atomicity over plain conditional writes:

* ``cas_ref`` is a read-compare-conditional-write loop: the version token
  (ETag) captured at read time guards the write, so a racing writer makes
  the conditional PUT fail with 412 instead of silently losing an update —
  the loop re-reads and either retries (value still matches ``expected``)
  or raises :class:`~repro.core.errors.RefConflict`.
* ``cas_refs`` preflights EVERY expectation (capturing version tokens)
  before writing anything — a stale expectation updates nothing — then
  applies token-guarded conditional writes; a mid-batch 412 (concurrent
  racer) rolls the already-applied refs back.  Unlike the server-side
  ``cas_refs`` of :class:`~repro.core.remote.RemoteServer` the
  conflict-then-rollback window is briefly visible to concurrent readers
  (S3 has no multi-key transaction), which is the same contract as the
  sync layer's per-ref fallback — and what the conformance matrix pins.

A transport fault *during* a conditional write raises
:class:`~repro.core.errors.AmbiguousRefUpdate` (the write may have landed;
see docs/remote_store.md), never a plain failure.

Real-endpoint readiness (docs/remote_store.md "Wire speed"):

* **SigV4 signing** — when credentials are present (keyword, URL userinfo,
  or ``AWS_ACCESS_KEY_ID``/``AWS_SECRET_ACCESS_KEY``), every request
  carries an ``Authorization`` header computed by
  :class:`~repro.core.sigv4.SigV4Signer`; the stub's verification mode
  proves the canonical-request math in CI.
* **Retryable 5xx** — 500/502/503/504 (S3 ``SlowDown`` throttling) retry
  with capped jittered backoff, but ONLY for idempotent requests: a
  conditional write is never blindly replayed, preserving the
  ``AmbiguousRefUpdate`` contract.
* **Multipart + ranged transfer** — payloads at or above
  ``multipart_threshold`` upload via initiate/part/complete (part-level
  retry for free since part PUTs are idempotent; any failure aborts the
  upload server-side so no orphaned parts accrue) and download via ranged
  GETs (a ``Range``-first probe: a 200 means the server ignored the header
  and sent everything — the clean downgrade path).

``tests/``'s :mod:`repro.core.s3stub` serves the same dialect from the
stdlib so the whole stack is testable with zero new dependencies.
"""

from __future__ import annotations

import random
import threading
import time
import urllib.parse
import xml.etree.ElementTree as ET
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

from . import sigv4
from .errors import (AmbiguousRefUpdate, ObjectNotFound, RefConflict,
                     RefNotFound, RemoteError)
from .store import decode_frame, encode_frame, sha256_hex

_OBJ_PREFIX = "objects/"
_REF_PREFIX = "refs/"
_CAS_ATTEMPTS = 4  # re-read/retry rounds before a contended CAS gives up

#: response statuses worth retrying (transient server-side): 500 internal,
#: 502/504 gateway, 503 SlowDown — S3's throttling signal
_RETRYABLE_STATUS = frozenset({500, 502, 503, 504})
_BACKOFF_CAP = 2.0  # seconds; per-sleep ceiling for the jittered backoff


def _object_key(digest: str) -> str:
    return f"{_OBJ_PREFIX}{digest[:2]}/{digest[2:]}"


def _digest_of_key(key: str) -> str:
    return key[len(_OBJ_PREFIX):].replace("/", "", 1)


def _ref_key(name: str) -> str:
    for part in name.split("/"):
        if not part or part.startswith("."):
            raise ValueError(f"bad ref name {name!r}")
    return _REF_PREFIX + name


def _local_name(tag: str) -> str:
    """XML tag without its namespace (real S3 responses are namespaced,
    the stub's are not — match both)."""
    return tag.rsplit("}", 1)[-1]


class S3Backend:
    """``StoreBackend`` over an S3-compatible REST endpoint.

    >>> remote = S3Backend("http://127.0.0.1:9000", "lake")
    >>> remote.put(b"blob")            # PUT objects/…, framed + compressed
    >>> remote.cas_ref("branch=main", None, digest)   # If-None-Match: *

    ``pool`` bounds the HEAD/GET/PUT fan-out used to batch ``has_many`` /
    ``get_many`` / ``put_many`` — the S3 dialect has no server-side batch
    ops, so batching is client-side concurrency over per-thread
    connections.
    """

    def __init__(self, endpoint: str, bucket: str, *, timeout: float = 30.0,
                 retries: int = 2, pool: int = 8, codec: str = "auto",
                 level: int = 3,
                 credentials: Optional[sigv4.Credentials] = None,
                 region: str = "us-east-1", style: str = "path",
                 multipart_threshold: int = 8 << 20,
                 part_size: int = 8 << 20,
                 backoff: float = 0.1):
        parsed = urllib.parse.urlsplit(endpoint)
        if parsed.scheme not in ("http", "https"):
            raise ValueError(f"unsupported endpoint scheme {parsed.scheme!r}")
        if not bucket or "/" in bucket:
            raise ValueError(f"bad bucket name {bucket!r}")
        if style not in ("path", "virtual"):
            raise ValueError(f"addressing style must be 'path' or "
                             f"'virtual', got {style!r}")
        self.endpoint = endpoint.rstrip("/")
        self.bucket = bucket
        self.scheme = parsed.scheme
        endpoint_host = parsed.hostname or "127.0.0.1"
        self.style = style
        # virtual-host addressing (real S3 default): the bucket rides the
        # hostname and drops out of the path; path style keeps /bucket/key
        # (MinIO/stub spelling)
        self.host = (f"{bucket}.{endpoint_host}" if style == "virtual"
                     else endpoint_host)
        self.port = parsed.port or (443 if parsed.scheme == "https" else 80)
        self.timeout = timeout
        self.retries = retries
        self.pool = max(1, pool)
        self.codec = codec
        self.level = level
        self.region = region
        self.multipart_threshold = max(1, multipart_threshold)
        self.part_size = max(1, part_size)
        self.backoff = backoff
        if credentials is None:
            credentials = sigv4.Credentials.from_env()
        self.credentials = credentials
        self._signer = (sigv4.SigV4Signer(credentials, region=region)
                        if credentials is not None else None)
        # what http.client will put in the Host header (port elided when
        # default for the scheme) — the signer must sign the exact bytes
        default_port = 443 if self.scheme == "https" else 80
        self._host_header = (self.host if self.port == default_port
                             else f"{self.host}:{self.port}")
        self._local = threading.local()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._executor_lock = threading.Lock()

    @classmethod
    def from_url(cls, url: str, **kw) -> "S3Backend":
        """``s3://[key:secret@]host[:port]/bucket[?region=R&style=S&secure=1]``
        → a configured backend.

        Credential precedence: explicit ``credentials=`` keyword, then URL
        userinfo, then the standard ``AWS_*`` environment variables (the
        constructor's fallback); no credentials anywhere → unsigned
        requests (the stub's default mode).  ``secure=1`` selects HTTPS
        (real endpoints); default is plain HTTP (stub/MinIO-in-CI)."""
        parsed = urllib.parse.urlsplit(url)
        if parsed.scheme != "s3":
            raise ValueError(f"not an s3 URL: {url!r}")
        bucket = parsed.path.strip("/")
        if not bucket:
            raise ValueError(f"s3 URL missing a bucket: {url!r}")
        host = parsed.hostname or "127.0.0.1"
        port = f":{parsed.port}" if parsed.port else ""
        params = dict(urllib.parse.parse_qsl(parsed.query,
                                             keep_blank_values=True))
        if "region" in params:
            kw.setdefault("region", params["region"])
        if "style" in params:
            kw.setdefault("style", params["style"])
        if kw.get("credentials") is None and parsed.username:
            kw["credentials"] = sigv4.Credentials(
                access_key=urllib.parse.unquote(parsed.username),
                secret_key=urllib.parse.unquote(parsed.password or ""))
        scheme = ("https" if params.get("secure", "").lower()
                  in ("1", "true", "yes") else "http")
        return cls(f"{scheme}://{host}{port}", bucket, **kw)

    # ----------------------------------------------------------- plumbing
    def _conn(self):
        conn = getattr(self._local, "conn", None)
        if conn is None:
            import http.client

            cls = (http.client.HTTPSConnection if self.scheme == "https"
                   else http.client.HTTPConnection)
            conn = cls(self.host, self.port, timeout=self.timeout)
            self._local.conn = conn
        return conn

    def _drop_conn(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            try:
                conn.close()
            finally:
                self._local.conn = None

    def _sleep_backoff(self, attempt: int) -> None:
        """Capped exponential backoff with full jitter — the polite
        response to a throttling 503 (a synchronized immediate retry from
        a whole fan-out pool is exactly what SlowDown asks us to stop)."""
        delay = min(_BACKOFF_CAP, self.backoff * (2 ** attempt))
        time.sleep(delay * random.random())

    def _request(self, method: str, key: str, *, body: Optional[bytes] = None,
                 headers: Optional[Dict[str, str]] = None,
                 query: Optional[Dict[str, str]] = None,
                 idempotent: bool = True):
        """One REST round-trip → ``(status, headers, body)``.

        Idempotent requests (everything except conditional writes) retry
        on transport faults AND on retryable 5xx responses (500/502/503/504
        — S3 throttling serves ``503 SlowDown``) with capped jittered
        backoff.  A conditional write is never blindly replayed: a
        transport fault mid-flight raises :class:`AmbiguousRefUpdate`
        because the server may have applied it, and a 5xx *response*
        (the server answered — the write was not applied) surfaces to the
        caller unretried."""
        # percent-encode the key (the server decodes): ref names may carry
        # spaces/%/?/# — sent raw they would break http.client, truncate at
        # the query separator, or alias with their decoded spelling.  The
        # SigV4 canonical-URI rule is "single-encode, sign what you send",
        # so the signer sees this exact string.
        key_path = "/" + sigv4.canonical_quote(key, safe="/") if key else ""
        if self.style == "virtual":
            path = key_path or "/"
        else:
            path = "/" + self.bucket + key_path
        query_pairs = sorted((query or {}).items())
        # canonical query encoding on the wire == what gets signed; also
        # round-trips continuation tokens with spaces/%/# intact (urlencode
        # would spell a space '+', which SigV4 never does)
        query_string = sigv4.canonical_query(query_pairs)
        target = path + ("?" + query_string if query_string else "")
        attempts = 1 + (self.retries if idempotent else 0)
        last: Optional[Exception] = None
        last_status: Optional[int] = None
        result = None
        for attempt in range(attempts):
            result = None
            send_headers = dict(headers or {})
            if self._signer is not None:
                # re-signed per attempt: x-amz-date stays fresh across
                # backoff sleeps
                send_headers.update(self._signer.sign(
                    method, self._host_header, path, query_pairs,
                    body or b""))
            conn = self._conn()
            try:
                conn.request(method, target, body=body,
                             headers=send_headers)
                resp = conn.getresponse()
                data = resp.read()
                # normalize header names: servers spell ETag/Etag/etag
                # differently, and a missed version token would break CAS
                result = (resp.status,
                          {k.lower(): v for k, v in resp.getheaders()}, data)
            except Exception as e:  # noqa: BLE001 - socket/http.client zoo
                self._drop_conn()
                last = e
                continue
            if (result[0] in _RETRYABLE_STATUS and idempotent
                    and attempt + 1 < attempts):
                last_status = result[0]
                self._sleep_backoff(attempt)
                continue
            return result
        if result is not None:
            return result  # final attempt still 5xx: caller raises
        if not idempotent:
            raise AmbiguousRefUpdate(
                f"{method} {key}: transport failed after a conditional "
                f"write may have been delivered ({last!r}); ref state is "
                "unknown — re-read to resolve") from last
        detail = (f"HTTP {last_status}" if last_status is not None
                  else repr(last))
        raise RemoteError(f"{method} {key}: transport failed after "
                          f"{attempts} attempts ({detail})") from last

    def close(self) -> None:
        self._drop_conn()
        with self._executor_lock:
            executor, self._executor = self._executor, None
        if executor is not None:
            executor.shutdown(wait=True)

    # ------------------------------------------------------------ objects
    def _encode(self, data: bytes) -> bytes:
        return encode_frame(data, codec=self.codec, level=self.level)

    def put(self, data: bytes) -> str:
        digest = sha256_hex(data)
        self._upload(_object_key(digest), self._encode(data), digest)
        return digest

    def put_parts(self, parts: Sequence[bytes]) -> str:
        # one PUT carries one body
        return self.put(b"".join(parts))

    def get(self, digest: str) -> bytes:
        data = decode_frame(self.get_encoded(digest),
                            what=f"object {digest}")
        if sha256_hex(data) != digest:  # never trust the wire
            raise ObjectNotFound(f"digest mismatch for {digest} from s3")
        return data

    def has(self, digest: str) -> bool:
        status, _h, _b = self._request("HEAD", _object_key(digest))
        if status == 200:
            return True
        if status == 404:
            return False
        # anything else (503 throttle, 403) must NOT read as "absent":
        # the GC mark phase trusts has(), and a swallowed server error
        # would let the sweep delete live objects
        raise RemoteError(f"head {digest}: HTTP {status}")

    def _fan_out(self, fn, items):
        """Run ``fn`` over ``items`` on a bounded pool (order-preserving).
        The pool is persistent per backend so worker threads keep their
        per-thread connections alive across calls (a sync moves many small
        chunks — a fresh pool per chunk would pay a TCP connect per worker
        per chunk and leak the old sockets to the GC)."""
        if len(items) <= 1:
            return [fn(x) for x in items]
        with self._executor_lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(max_workers=self.pool)
            pool = self._executor
        return list(pool.map(fn, items))

    def has_many(self, digests: Iterable[str]) -> Set[str]:
        digests = list(digests)
        present = self._fan_out(self.has, digests)
        return {d for d, ok in zip(digests, present) if ok}

    def get_many(self, digests: Sequence[str]) -> Dict[str, bytes]:
        digests = list(digests)
        return dict(zip(digests, self._fan_out(self.get, digests)))

    def put_many(self, blobs: Sequence[bytes]) -> List[str]:
        return self._fan_out(self.put, list(blobs))

    def size(self, digest: str) -> int:
        """Stored (framed/compressed) size, same semantics as the
        filesystem store's on-disk size."""
        status, headers, _b = self._request("HEAD", _object_key(digest))
        if status != 200:
            raise ObjectNotFound(digest)
        return int(headers.get("content-length", 0))

    def mtime(self, digest: str) -> float:
        """Upload time from the ``Last-Modified`` response header — the
        age source for the GC grace window over S3.  A server that omits
        the header reads as *just uploaded* (never sweepable inside the
        window): the failure mode of missing age data must be "kept a
        garbage blob another hour", never "deleted an in-flight upload"."""
        return self.stat(digest)[1]

    def stat(self, digest: str) -> Tuple[int, float]:
        """``(stored size, Last-Modified)`` from ONE HEAD request — the
        per-candidate cost of a grace-window sweep over the dialect."""
        import email.utils
        import time as _time

        status, headers, _b = self._request("HEAD", _object_key(digest))
        if status == 404:
            raise ObjectNotFound(digest)
        if status != 200:
            raise RemoteError(f"head {digest}: HTTP {status}")
        size = int(headers.get("content-length", 0))
        stamp = headers.get("last-modified")
        if not stamp:
            return size, _time.time()
        try:
            return size, email.utils.parsedate_to_datetime(
                stamp).timestamp()
        except (TypeError, ValueError):
            return size, _time.time()

    def touch_many(self, digests: Sequence[str]) -> int:
        """S3 has no cheap mtime refresh (a self-copy per object would
        cost a mutating request each) — report 0 touched; pushes that
        dedup against an S3 remote stay protected by the GC generation
        token's retry path instead."""
        return 0

    def delete_object(self, digest: str) -> bool:
        """Remote-side GC sweep primitive.  Idempotent: missing → False."""
        status, _h, _b = self._request("DELETE", _object_key(digest))
        if status in (200, 204):
            return True
        if status == 404:
            return False
        raise RemoteError(f"delete {digest}: HTTP {status}")

    # ------------------------------------------------- large-blob transfer
    def _upload(self, key: str, payload: bytes, what: str) -> None:
        """Simple PUT below the multipart threshold, initiate/part/complete
        at or above it."""
        if len(payload) >= self.multipart_threshold:
            self._put_multipart(key, payload, what)
            return
        status, _h, _b = self._request("PUT", key, body=payload)
        if status not in (200, 201, 204):
            raise RemoteError(f"put {what}: HTTP {status}")

    def _put_multipart(self, key: str, payload: bytes, what: str) -> None:
        """Multipart upload with abort-on-failure.

        Part PUTs are idempotent (same bytes to the same part number), so
        they ride ``_request``'s retry loop for free.  ANY failure after
        initiation aborts the upload server-side — a crashed push must not
        leave orphaned parts accruing storage charges."""
        status, _h, body = self._request("POST", key, body=b"",
                                         query={"uploads": ""})
        if status != 200:
            raise RemoteError(f"multipart initiate {what}: HTTP {status}")
        upload_id = None
        try:
            root = ET.fromstring(body)
        except ET.ParseError as e:
            raise RemoteError(
                f"multipart initiate {what}: malformed XML ({e})") from e
        for el in root.iter():
            if _local_name(el.tag) == "UploadId" and el.text:
                upload_id = el.text.strip()
                break
        if not upload_id:
            raise RemoteError(f"multipart initiate {what}: no UploadId")
        try:
            part_numbers: List[int] = []
            for off in range(0, len(payload), self.part_size):
                number = off // self.part_size + 1
                status, _h, _b = self._request(
                    "PUT", key, body=payload[off:off + self.part_size],
                    query={"uploadId": upload_id,
                           "partNumber": str(number)})
                if status not in (200, 201, 204):
                    raise RemoteError(
                        f"multipart part {number} of {what}: HTTP {status}")
                part_numbers.append(number)
            complete = ("<CompleteMultipartUpload>" + "".join(
                f"<Part><PartNumber>{n}</PartNumber></Part>"
                for n in part_numbers) +
                "</CompleteMultipartUpload>").encode()
            status, _h, _b = self._request(
                "POST", key, body=complete, query={"uploadId": upload_id})
            if status != 200:
                raise RemoteError(
                    f"multipart complete {what}: HTTP {status}")
        except BaseException:
            try:  # best-effort abort: no orphaned parts
                self._request("DELETE", key,
                              query={"uploadId": upload_id})
            except Exception:  # noqa: BLE001 - original error wins
                pass
            raise

    @staticmethod
    def _content_range_total(value: Optional[str]) -> Optional[int]:
        """``bytes 0-99/1234`` → 1234 (None when absent/opaque)."""
        if not value or "/" not in value:
            return None
        total = value.rsplit("/", 1)[1].strip()
        return int(total) if total.isdigit() else None

    # -------------------------------------------------- encoded payloads
    def get_encoded(self, digest: str) -> bytes:
        """Framed payload fetch via ranged GET.

        The first request carries ``Range: bytes=0-(part_size-1)`` as a
        probe: a 200 means the server ignored the header and sent the
        whole object (the downgrade path — old stubs, simple proxies); a
        206 carries ``Content-Range`` naming the total, and the remainder
        streams in sequential ``part_size`` ranges (each idempotent, so a
        dropped connection re-fetches one range, not the whole blob).
        Ranges are fetched on the calling thread — ``get_many_encoded``
        already fans out per blob and nesting pools would deadlock."""
        key = _object_key(digest)
        status, headers, body = self._request(
            "GET", key, headers={"Range": f"bytes=0-{self.part_size - 1}"})
        if status == 404:
            raise ObjectNotFound(digest)
        if status == 200:
            return body  # server ignored Range: whole object in one go
        if status != 206:
            raise RemoteError(f"get {digest}: HTTP {status}")
        total = self._content_range_total(headers.get("content-range"))
        if total is None or total <= len(body):
            return body
        parts = [body]
        got = len(body)
        while got < total:
            end = min(got + self.part_size, total) - 1
            status, _h, chunk = self._request(
                "GET", key, headers={"Range": f"bytes={got}-{end}"})
            if status == 200:
                return chunk  # downgraded mid-flight: full body came back
            if status != 206 or not chunk:
                raise RemoteError(
                    f"get {digest}: ranged fetch at {got} → HTTP {status}")
            parts.append(chunk)
            got += len(chunk)
        return b"".join(parts)

    def put_encoded(self, payload: bytes) -> str:
        # decode to learn + verify the digest, upload the ORIGINAL payload:
        # compression paid at the source is never re-paid here
        digest = sha256_hex(decode_frame(payload, what="encoded payload"))
        self._upload(_object_key(digest), payload, digest)
        return digest

    def get_many_encoded(self, digests: Sequence[str]) -> Dict[str, bytes]:
        digests = list(digests)
        return dict(zip(digests, self._fan_out(self.get_encoded, digests)))

    def put_many_encoded(self, payloads: Sequence[bytes],
                         digests: Optional[Sequence[str]] = None
                         ) -> List[str]:
        # the digest hint is ignored: the S3 dialect has no server-side
        # verification, so the client-side decode here is the only check
        # standing between a corrupt payload and the bucket
        return self._fan_out(self.put_encoded, list(payloads))

    # ------------------------------------------------------------ listing
    def _list_keys(self, prefix: str, *, start_after: Optional[str],
                   limit: int) -> Tuple[List[str], bool]:
        """One ListObjectsV2-style page: ``(sorted keys, truncated)``.

        Truncation comes from the response's ``IsTruncated`` field, never
        from comparing the page size to ``limit`` — servers cap max-keys
        (S3: 1000), so a short page can still have more behind it."""
        query = {"list-type": "2", "prefix": prefix,
                 "max-keys": str(max(1, limit))}
        if start_after:
            query["start-after"] = start_after
        status, _h, body = self._request("GET", "", query=query)
        if status != 200:
            raise RemoteError(f"list {prefix!r}: HTTP {status}")
        try:
            root = ET.fromstring(body)
        except ET.ParseError as e:
            raise RemoteError(f"list {prefix!r}: malformed XML ({e})") from e
        keys: List[str] = []
        truncated = False
        for el in root.iter():
            name = _local_name(el.tag)
            if name == "Contents":
                for child in el:
                    if _local_name(child.tag) == "Key":
                        keys.append(child.text or "")
            elif name == "IsTruncated":
                truncated = (el.text or "").strip().lower() == "true"
        return keys, truncated

    def list_objects(self, *, page_token: Optional[str] = None,
                     limit: int = 1000) -> Tuple[List[str], Optional[str]]:
        limit = max(1, limit)
        start = _object_key(page_token) if page_token else None
        keys, truncated = self._list_keys(_OBJ_PREFIX, start_after=start,
                                          limit=limit)
        page = [_digest_of_key(k) for k in keys]
        return page, (page[-1] if page and truncated else None)

    def iter_objects(self) -> Iterator[str]:
        token: Optional[str] = None
        while True:
            page, token = self.list_objects(page_token=token)
            yield from page
            if token is None:
                return

    # --------------------------------------------------------------- refs
    def _read_ref(self, name: str) -> Tuple[Optional[str], Optional[str]]:
        """Current ``(value, version_token)`` of a ref; (None, None) when
        it does not exist.  The token guards conditional writes."""
        status, headers, body = self._request("GET", _ref_key(name))
        if status == 404:
            return None, None
        if status != 200:
            raise RemoteError(f"get_ref {name}: HTTP {status}")
        return body.decode().strip(), headers.get("etag")

    def get_ref(self, name: str) -> str:
        value, _etag = self._read_ref(name)
        if value is None:
            raise RefNotFound(name)
        return value

    def set_ref(self, name: str, digest: str) -> None:
        status, _h, _b = self._request(
            "PUT", _ref_key(name), body=digest.encode())
        if status not in (200, 201, 204):
            raise RemoteError(f"set_ref {name}: HTTP {status}")

    def delete_ref(self, name: str) -> None:
        status, _h, _b = self._request("DELETE", _ref_key(name))
        if status == 404:
            raise RefNotFound(name)
        if status not in (200, 204):
            raise RemoteError(f"delete_ref {name}: HTTP {status}")

    def _conditional_put(self, name: str, digest: str,
                         etag: Optional[str]) -> Tuple[bool, Optional[str]]:
        """Token-guarded ref write: ``If-Match`` against the captured
        version, ``If-None-Match: *`` for create-only.  Returns
        ``(applied, new_etag)``; False means 412 (a racer moved the ref
        between our read and this write)."""
        headers = ({"If-Match": etag} if etag is not None
                   else {"If-None-Match": "*"})
        status, resp_headers, _b = self._request(
            "PUT", _ref_key(name), body=digest.encode(), headers=headers,
            idempotent=False)
        if status == 412:
            return False, None
        if status not in (200, 201, 204):
            raise RemoteError(f"cas_ref {name}: HTTP {status}")
        return True, resp_headers.get("etag")

    def _conditional_delete(self, name: str, etag: str) -> None:
        """Token-guarded ref delete (rollback of a create): 412 means a
        racer moved the ref since our write — their update stays."""
        status, _h, _b = self._request(
            "DELETE", _ref_key(name), headers={"If-Match": etag},
            idempotent=False)
        if status not in (200, 204, 404, 412):
            raise RemoteError(f"conditional delete {name}: HTTP {status}")

    def cas_ref(self, name: str, expected: Optional[str], new: str) -> None:
        """Compare-and-set via conditional write.

        Value semantics match :meth:`ObjectStore.cas_ref` exactly: the
        *current value* is compared against ``expected``; the version
        token only makes the read-compare-write atomic (a 412 from a
        concurrent writer re-reads instead of clobbering)."""
        for _ in range(_CAS_ATTEMPTS):
            current, etag = self._read_ref(name)
            if current != expected:
                raise RefConflict(
                    f"ref {name}: expected {expected!r}, found {current!r}")
            applied, _new_etag = self._conditional_put(name, new, etag)
            if applied:
                return
        raise RefConflict(
            f"ref {name}: conditional write kept losing races "
            f"({_CAS_ATTEMPTS} attempts)")

    def cas_refs(self, updates: Sequence[Tuple[str, Optional[str], str]]
                 ) -> None:
        """Multi-ref CAS over conditional writes.

        Every expectation is validated (and its version token captured)
        before ANY write — one stale expectation updates nothing.  The
        token-guarded writes then apply in order; a mid-batch 412 from a
        concurrent racer rolls the applied prefix back.  See the module
        docstring for how this differs from a server-side transactional
        ``cas_refs``."""
        tokens: List[Optional[str]] = []
        for name, expected, _new in updates:
            current, etag = self._read_ref(name)
            if current != expected:
                raise RefConflict(
                    f"ref {name}: expected {expected!r}, found {current!r} "
                    "(no ref in this batch was updated)")
            tokens.append(etag)
        applied: List[Tuple[str, Optional[str], Optional[str]]] = []
        for (name, expected, new), etag in zip(updates, tokens):
            try:
                ok, new_etag = self._conditional_put(name, new, etag)
            except AmbiguousRefUpdate:
                # the write may have landed before the fault: resolve by
                # re-read so a mid-batch fault can never leave the prefix
                # torn behind an "unknown" diagnosis
                try:
                    current, cur_etag = self._read_ref(name)
                except RemoteError:
                    self._rollback(applied)
                    raise
                if current == new:
                    ok, new_etag = True, cur_etag  # it DID apply: continue
                else:
                    self._rollback(applied)
                    raise RemoteError(
                        f"ref {name}: transport fault during conditional "
                        "write; the ref was re-read and verified unchanged "
                        "— applied refs were rolled back") from None
            except RemoteError:
                self._rollback(applied)
                raise
            if not ok:
                self._rollback(applied)
                raise RefConflict(
                    f"ref {name}: lost a race mid-batch; already-applied "
                    "refs were rolled back")
            applied.append((name, expected, new_etag))

    def _rollback(self, applied) -> None:
        """Best-effort restore of already-applied conditional writes."""
        for name, expected, new_etag in reversed(applied):
            try:
                if expected is None:
                    # we created it: undo is a delete — guarded by OUR
                    # write's token, so a racer who CASed the ref onward
                    # since keeps their committed update (412, not clobber)
                    if new_etag is not None:
                        self._conditional_delete(name, new_etag)
                    else:
                        self.delete_ref(name)
                else:
                    # guarded by OUR write's token: if a racer moved the
                    # ref since, the 412 leaves their update in place
                    self._conditional_put(name, expected, new_etag)
            except (RemoteError, RefConflict, RefNotFound):
                pass  # best effort: the racer's update wins

    def iter_refs(self, prefix: str = "") -> Iterator[str]:
        token: Optional[str] = None
        while True:
            page, token = self.list_refs(prefix, page_token=token)
            for name, _digest in page:
                yield name
            if token is None:
                return

    def list_refs(self, prefix: str = "", *,
                  page_token: Optional[str] = None, limit: int = 1000
                  ) -> Tuple[List[Tuple[str, str]], Optional[str]]:
        limit = max(1, limit)
        start = _REF_PREFIX + page_token if page_token else None
        keys, truncated = self._list_keys(_REF_PREFIX + prefix,
                                          start_after=start, limit=limit)
        names = [k[len(_REF_PREFIX):] for k in keys]
        values = self._fan_out(lambda n: self._read_ref(n)[0], names)
        page = [(n, v) for n, v in zip(names, values) if v is not None]
        return page, (names[-1] if names and truncated else None)
