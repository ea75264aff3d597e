"""Remote object-store tier: the "S3 + Nessie service" half of the paper.

The local :class:`~repro.core.store.ObjectStore` makes a replay reproducible
*on the host that ran it*; this module is what makes it reproducible anywhere.
Three pieces:

``RemoteServer`` / transports
    A server speaking the :class:`~repro.core.store.StoreBackend` wire
    contract over msgpack request/response dicts.  The operations map 1:1
    onto an S3-style service (keys are the same ``objects/ab/cdef...``
    layout the filesystem store uses):

        ================  ===========================================
        wire op           S3/real-service equivalent
        ================  ===========================================
        put_object        PutObject (idempotent: content addressed)
        get_object        GetObject (digest-verified by the client)
        head_objects      batched HeadObject
        get_objects       batched GetObject (one frame per leaf chunk)
        put_objects       batched PutObject (one frame per leaf chunk)
        get_objects_…     …_encoded: batched GetObject of framed at-rest
                          payloads (compressed wire frames)
        put_objects_…     …_encoded: batched PutObject of framed payloads
                          (decoded + digest-verified server-side)
        has_chunks        chunk-level HeadObject: which content-defined
                          chunk hashes the server can already resolve
                          (bounded index over its own large blobs)
        put_objects_delta batched PutObject of delta *recipes* — literal
                          runs + references to chunks the server already
                          holds; reassembled, re-hashed per chunk and
                          digest-verified server-side.  Unresolvable
                          references answer "stale", never an error: the
                          client re-sends those blobs whole-frame
                          (see repro.core.delta)
        delete_object     DeleteObject (remote-side GC sweep; clients
                          must opt in with allow_delete=True)
        stat_object       HeadObject (size + Last-Modified — what the
                          GC grace window compares upload ages against)
        gc_mark           server-side GC mark: the server walks its OWN
                          refs/objects (no per-object wire reads), bumps
                          the generation token, stashes the live set
        gc_sweep          server-side sweep under a gc_mark token, with
                          the upload-age grace window applied locally
        list_objects      ListObjectsV2 w/ ContinuationToken
        get_ref/set_ref   tiny pointer objects
        cas_ref           conditional put (DynamoDB / If-Match)
        cas_refs          transactional multi-item conditional write
        list_refs         paged pointer listing (name, digest) pairs
        ================  ===========================================

    Two transports ship: ``LoopbackTransport`` (in-process, still goes
    through a full msgpack encode/decode so only wire-safe types survive)
    and ``HTTPTransport`` + :func:`serve_http` (stdlib http.server loopback
    — one POST endpoint carrying msgpack bodies).

``RemoteStore``
    The client: implements ``StoreBackend``, so catalogs, run caches,
    ledgers and the sync layer use a remote exactly like a local directory.
    Idempotent requests retry on transient transport faults.

``TieredStore``
    local→remote read-through with local write-back: ``get`` serves from
    the local tier, faults to the remote and persists the blob locally;
    refs read local-first with remote fallback; all writes land locally.
    A warm run-cache hit on host B can therefore reuse host A's node
    outputs without an explicit pull (see docs/remote_store.md for the
    trust model).
"""

from __future__ import annotations

import threading
from typing import (Any, Dict, Iterable, Iterator, List, Optional, Sequence,
                    Set, Tuple)

import msgpack

from . import delta as _delta
from .errors import (AmbiguousRefUpdate, CodecUnavailable, ObjectNotFound,
                     RefConflict, RefNotFound, RemoteError, ReproError)
from .store import (ObjectStore, StoreBackend, decode_frame, frame_raw,
                    sha256_hex)

#: ref value meaning "must not exist" in wire CAS (msgpack has no Optional
#: on the sentinel side of If-Match semantics)
_ABSENT = ""


def _pack(obj) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def _unpack(blob: bytes):
    return msgpack.unpackb(blob, raw=False)


# --------------------------------------------------------------------- server
class RemoteServer:
    """Serves the wire contract over any :class:`StoreBackend` (usually a
    plain filesystem :class:`ObjectStore` — which is exactly what makes the
    S3-key layout claim true: the served tree IS the S3 key scheme)."""

    def __init__(self, store: StoreBackend):
        self.store = store
        # Pending server-side GC marks.  REAL marks (a generation bump
        # will follow with a sweep) are persisted in the served store
        # itself — mark blob in the object keyspace, ``gc/mark/<token>``
        # ref pointing at it — so a server restart between gc_mark and
        # gc_sweep no longer aborts the collection (any server instance
        # over the same store can finish it).  Bounded to the
        # ``_GC_MARK_KEEP`` most recent so a crashed GC client cannot
        # leak unbounded live sets.  DRY-RUN marks stay process-local:
        # a dry run must not write anything, so its token only has to
        # outlive the immediate dry sweep that consumes it.
        self._gc_marks: Dict[str, set] = {}
        self._gc_nonce = 0
        self._gc_lock = threading.Lock()
        # chunk hash → location over this server's large blobs: what lets
        # a sender ship delta recipes instead of whole frames.  Purely an
        # accelerator (bounded LRU, every hit re-verified) — an empty or
        # stale index costs wire bytes, never correctness.
        self.chunks = _delta.ChunkIndex()

    _GC_MARK_REF_PREFIX = "gc/mark/"
    _GC_MARK_KEEP = 4

    # ---------------------------------------------- persistent mark blobs
    def _pending_marks(self) -> List[Tuple[str, str]]:
        """(token, mark blob digest) of every persisted, unconsumed mark."""
        out = []
        for ref in self.store.iter_refs(self._GC_MARK_REF_PREFIX):
            try:
                out.append((ref[len(self._GC_MARK_REF_PREFIX):],
                            self.store.get_ref(ref)))
            except RefNotFound:  # consumed by a concurrent sweep
                continue
        return out

    def _drop_mark(self, token: str, digest: Optional[str]) -> None:
        """Consume a persisted mark: ref first (the consumption point),
        then the blob unless another pending mark shares it (identical
        live sets content-address to the same blob)."""
        try:
            self.store.delete_ref(self._GC_MARK_REF_PREFIX + token)
        except RefNotFound:
            pass
        if digest is not None and all(d != digest
                                      for _t, d in self._pending_marks()):
            self.store.delete_object(digest)

    # Each op returns a plain dict; errors are returned (not raised) so the
    # transport layer stays exception-free and HTTP responses stay 200.
    def handle(self, request: Dict[str, Any]) -> Dict[str, Any]:
        try:
            op = request.get("op")
            fn = getattr(self, f"_op_{op}", None)
            if fn is None:
                return {"error": "bad_request", "message": f"unknown op {op!r}"}
            return fn(request)
        except CodecUnavailable as e:
            # before ObjectNotFound (its superclass): the client falls back
            # to raw transfer on this one instead of treating it as missing
            return {"error": "codec_unavailable", "message": str(e)}
        except ObjectNotFound as e:
            return {"error": "object_not_found", "message": str(e)}
        except RefNotFound as e:
            return {"error": "ref_not_found", "message": str(e)}
        except RefConflict as e:
            return {"error": "ref_conflict", "message": str(e)}
        except (KeyError, TypeError, ValueError) as e:
            return {"error": "bad_request", "message": repr(e)}

    def handle_bytes(self, payload: bytes) -> bytes:
        """msgpack-framed entry point shared by every transport."""
        try:
            request = _unpack(payload)
        except Exception as e:  # noqa: BLE001 - malformed frame
            return _pack({"error": "bad_request", "message": repr(e)})
        return _pack(self.handle(request))

    # objects -----------------------------------------------------------
    def _index_blob(self, digest: str, data: bytes) -> None:
        """Feed the chunk index on arrival — only blobs big enough that a
        future delta against them could beat a whole frame."""
        if len(data) >= _delta.DELTA_MIN_BYTES:
            self.chunks.add_blob(digest, data)

    def _op_put_object(self, req):
        data = req["data"]
        digest = req["digest"]
        if sha256_hex(data) != digest:
            return {"error": "bad_request",
                    "message": f"content does not hash to {digest}"}
        # idempotent: ObjectStore.put dedups on existing digests
        got = self.store.put(data)
        self._index_blob(digest, data)
        return {"digest": got}

    def _op_get_object(self, req):
        return {"data": self.store.get(req["digest"])}

    def _op_head_objects(self, req):
        return {"present": sorted(self.store.has_many(req["digests"]))}

    def _op_get_objects(self, req):
        # batched GetObject: one frame carries a whole leaf chunk, so a
        # closure transfer pays one round-trip per chunk, not per blob
        return {"objects": [[d, self.store.get(d)] for d in req["digests"]]}

    def _op_put_objects(self, req):
        digests = []
        for digest, data in req["objects"]:
            if sha256_hex(data) != digest:
                return {"error": "bad_request",
                        "message": f"content does not hash to {digest}"}
            digests.append(self.store.put(data))
            self._index_blob(digest, data)
        return {"digests": digests}

    def _op_get_objects_encoded(self, req):
        # batched GetObject of FRAMED payloads: a blob compressed at rest
        # on the serving store crosses the wire in that form — the client
        # decodes once (verification + accounting) and never recompresses
        get_encoded = getattr(self.store, "get_encoded", None)
        if get_encoded is None:  # backend without at-rest framing
            return {"objects": [[d, frame_raw(self.store.get(d))]
                                for d in req["digests"]]}
        return {"objects": [[d, get_encoded(d)] for d in req["digests"]]}

    def _op_put_objects_encoded(self, req):
        # decode HERE (server-side verification was always part of this
        # op's contract) so the raw bytes can also feed the chunk index —
        # these are exactly the blobs a follow-up checkpoint push will
        # want to delta against
        put_many_encoded = getattr(self.store, "put_many_encoded", None)
        digests = []
        for digest, payload in req["objects"]:
            data = decode_frame(payload, what="encoded payload")
            if sha256_hex(data) != digest:
                return {"error": "bad_request",
                        "message": f"payload does not hash to {digest}"}
            if put_many_encoded is not None:
                # store the original frame (compression already paid at
                # the source); the digest hint skips stores' re-decode
                # where they honor it
                got = put_many_encoded([payload], digests=[digest])[0]
            else:
                got = self.store.put(data)
            if got != digest:
                return {"error": "bad_request",
                        "message": f"store acknowledged {got}, "
                                   f"expected {digest}"}
            self._index_blob(digest, data)
            digests.append(got)
        return {"digests": digests}

    def _op_has_chunks(self, req):
        # chunk-level has_many: the one round-trip that decides how much
        # of each blob a delta push can leave out
        return {"present": sorted(self.chunks.has(req["hashes"]))}

    def _op_put_objects_delta(self, req):
        # batched delta put: reassemble each recipe against the chunk
        # index + our own store, verify chunk-by-chunk AND whole-blob,
        # store like any other put.  A reference we can no longer resolve
        # (evicted index entry, GC'd blob) makes that blob "stale" — the
        # client re-sends it whole-frame; it is never an error.
        digests: List[str] = []
        stale: List[str] = []
        blob_cache: Dict[str, bytes] = {}
        for digest, recipe in req["objects"]:
            try:
                data = _delta.assemble(recipe, self.chunks, self.store.get,
                                       blob_cache)
            except ObjectNotFound:
                stale.append(digest)
                continue
            if sha256_hex(data) != digest:
                return {"error": "bad_request",
                        "message": f"delta recipe does not reassemble "
                                   f"to {digest}"}
            self.store.put(data)
            self._index_blob(digest, data)
            digests.append(digest)
        return {"digests": digests, "stale": stale}

    def _op_delete_object(self, req):
        # remote-side GC sweep (repro gc --remote): the only mutation of
        # the object keyspace the protocol exposes — clients must opt in
        # (RemoteStore(allow_delete=True)), so a tier-mounted client can
        # still never collect from the shared remote by accident
        return {"deleted": bool(self.store.delete_object(req["digest"]))}

    def _op_list_objects(self, req):
        page, nxt = self.store.list_objects(
            page_token=req.get("token") or None,
            limit=int(req.get("limit") or 1000))
        return {"digests": page, "next": nxt}

    def _op_size_object(self, req):
        return {"size": self.store.size(req["digest"])}

    def _op_stat_object(self, req):
        # size + upload mtime in one round-trip: what a client-side GC
        # sweep needs per candidate to honor the --prune-age grace window
        # against a server without gc_mark/gc_sweep
        digest = req["digest"]
        return {"size": self.store.size(digest),
                "mtime": float(self.store.mtime(digest))}

    def _op_touch_objects(self, req):
        # batched mtime refresh (sync touch-on-dedup): resets the grace-
        # window clock on objects a push deduplicated against, so they
        # cannot age out while the rest of the closure uploads
        touch = getattr(self.store, "touch_many", None)
        if touch is None:  # backend without cheap touch: report 0
            return {"touched": 0}
        return {"touched": int(touch(req["digests"]))}

    # ----------------------------------------------------- server-side GC
    def _op_gc_mark(self, req):
        # the whole mark phase runs HERE, over the server's own store: no
        # per-object wire reads.  Bumps the GC generation token first (not
        # on dry runs — nothing will be deleted) so concurrent pushes that
        # captured the old token fail their cas_refs cleanly.
        from .gc import mark_live
        from .store import bump_generation

        dry_run = bool(req.get("dry_run"))
        if dry_run:
            # a nonce token, NOT the shared generation: a dry run must
            # neither bump the generation nor collide with (and later
            # consume) a real mark pending its sweep.  Kept in process
            # memory — a dry run writes nothing to the store.
            with self._gc_lock:
                self._gc_nonce += 1
                token = f"dry-{self._gc_nonce}"
        else:
            token = bump_generation(self.store)
        live = mark_live(self.store, drop_cache=bool(req.get("drop_cache")),
                         dry_run=dry_run)
        if dry_run:
            with self._gc_lock:
                self._gc_marks[token] = live
                while len(self._gc_marks) > self._GC_MARK_KEEP:
                    self._gc_marks.pop(next(iter(self._gc_marks)))
        else:
            # persist the mark: blob in the object keyspace, consumed by
            # the sweep — which may run on a different server instance
            digest = self.store.put(_pack({"live": sorted(live)}))
            self.store.set_ref(self._GC_MARK_REF_PREFIX + token, digest)
            # prune abandoned marks beyond the newest _GC_MARK_KEEP
            # (generation tokens are monotonically increasing integers)
            pending = sorted(self._pending_marks(),
                             key=lambda td: int(td[0]))
            for old_token, old_digest in pending[:-self._GC_MARK_KEEP]:
                self._drop_mark(old_token, old_digest)
        return {"generation": token, "live": len(live)}

    def _op_gc_sweep(self, req):
        from .gc import sweep

        generation = req["generation"]
        with self._gc_lock:
            live = self._gc_marks.pop(generation, None)
        mark_digest: Optional[str] = None
        if live is None:  # not a dry token: look up the persisted mark
            try:
                mark_digest = self.store.get_ref(
                    self._GC_MARK_REF_PREFIX + generation)
                live = set(_unpack(self.store.get(mark_digest))["live"])
            except RefNotFound:
                return {"error": "bad_request",
                        "message": f"unknown gc generation {generation!r} "
                                   "(run gc_mark first)"}
            except ObjectNotFound:
                self._drop_mark(generation, None)
                return {"error": "bad_request",
                        "message": f"gc mark {generation!r} expired "
                                   "(collected by a concurrent sweep); "
                                   "run gc_mark again"}
        # pending mark blobs are GC bookkeeping, not garbage: keep every
        # one (including ours) out of this sweep's candidate set
        keep = live | {d for _t, d in self._pending_marks()}
        swept, freed, young = sweep(
            self.store, keep,
            prune_age=float(req.get("prune_age") or 0.0),
            dry_run=bool(req.get("dry_run")))
        if mark_digest is not None and not bool(req.get("dry_run")):
            self._drop_mark(generation, mark_digest)
        return {"swept": swept, "bytes_freed": freed,
                "skipped_young": young}

    # refs --------------------------------------------------------------
    def _op_get_ref(self, req):
        return {"digest": self.store.get_ref(req["name"])}

    def _op_set_ref(self, req):
        self.store.set_ref(req["name"], req["digest"])
        return {}

    def _op_cas_ref(self, req):
        expected = req.get("expected", _ABSENT)
        self.store.cas_ref(req["name"],
                           None if expected == _ABSENT else expected,
                           req["new"])
        return {}

    def _op_cas_refs(self, req):
        # server-side multi-ref CAS: the whole batch commits or none of it
        # does, under the backing store's ref guard (multi-ref push atomicity
        # holds even with two servers fronting one tree)
        self.store.cas_refs([
            (name, None if expected == _ABSENT else expected, new)
            for name, expected, new in req["updates"]])
        return {}

    def _op_delete_ref(self, req):
        self.store.delete_ref(req["name"])
        return {}

    def _op_list_refs(self, req):
        page, nxt = self.store.list_refs(
            req.get("prefix") or "",
            page_token=req.get("token") or None,
            limit=int(req.get("limit") or 1000))
        return {"refs": [[n, d] for n, d in page], "next": nxt}


# ----------------------------------------------------------------- transports
class LoopbackTransport:
    """In-process transport.  Still round-trips through msgpack so requests
    are held to exactly what the wire can carry."""

    def __init__(self, server: RemoteServer):
        self.server = server

    def request(self, payload: bytes) -> bytes:
        return self.server.handle_bytes(payload)

    def close(self) -> None:
        pass


class HTTPTransport:
    """Client side of the HTTP loopback: POST msgpack frames to ``/rpc``.

    Connections are per-thread (http.client is not thread-safe) so a
    ``--jobs N`` executor can fault blobs concurrently through one store.
    """

    def __init__(self, url: str, *, timeout: float = 30.0):
        import urllib.parse

        parsed = urllib.parse.urlsplit(url)
        if parsed.scheme not in ("http", "https"):
            raise ValueError(f"unsupported scheme {parsed.scheme!r}")
        self.scheme = parsed.scheme
        self.host = parsed.hostname or "127.0.0.1"
        self.port = parsed.port or (443 if parsed.scheme == "https" else 80)
        self.timeout = timeout
        self._local = threading.local()

    def _conn(self):
        conn = getattr(self._local, "conn", None)
        if conn is None:
            import http.client

            cls = (http.client.HTTPSConnection if self.scheme == "https"
                   else http.client.HTTPConnection)
            conn = cls(self.host, self.port, timeout=self.timeout)
            self._local.conn = conn
        return conn

    def request(self, payload: bytes) -> bytes:
        conn = self._conn()
        try:
            conn.request("POST", "/rpc", body=payload,
                         headers={"Content-Type": "application/x-msgpack",
                                  "Content-Length": str(len(payload))})
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise RemoteError(f"HTTP {resp.status} from remote")
            return body
        except RemoteError:
            # drop the (possibly wedged) connection; retry policy lives in
            # RemoteStore, not here
            self.close()
            raise
        except Exception as e:  # http.client + socket raise a small zoo;
            # normalize to RemoteError so RemoteStore's idempotent-op retry
            # sees every transient fault (ECONNREFUSED, ECONNRESET, ...)
            self.close()
            raise RemoteError(f"transport failure: {e!r}") from e

    def close(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            try:
                conn.close()
            finally:
                self._local.conn = None


def serve_http(store: StoreBackend, *, host: str = "127.0.0.1",
               port: int = 0):
    """Start a loopback HTTP server for ``store`` on a daemon thread.

    Returns ``(httpd, url)``; ``port=0`` picks a free port.  Call
    ``httpd.shutdown()`` to stop (tests) or ``httpd.serve_forever()`` is
    already running so just keep the process alive (``repro serve``).
    """
    import http.server

    server = RemoteServer(store)

    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_POST(self):  # noqa: N802 - stdlib naming
            if self.path != "/rpc":
                self.send_error(404)
                return
            length = int(self.headers.get("Content-Length", 0))
            body = server.handle_bytes(self.rfile.read(length))
            self.send_response(200)
            self.send_header("Content-Type", "application/x-msgpack")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # quiet: tests run many requests
            pass

    httpd = http.server.ThreadingHTTPServer((host, port), Handler)
    httpd.daemon_threads = True
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    url = f"http://{httpd.server_address[0]}:{httpd.server_address[1]}"
    return httpd, url


# ----------------------------------------------------------------- the client
_RETRYABLE_OPS = frozenset({
    # all idempotent: re-sending after an ambiguous failure cannot corrupt
    # state.  cas_ref / cas_refs are deliberately NOT here — a retry after
    # a success that was lost in transit would double-apply the swap.
    "put_object", "get_object", "head_objects", "list_objects",
    "get_objects", "put_objects",
    "get_objects_encoded", "put_objects_encoded", "delete_object",
    # delta ops are idempotent too: has_chunks reads, and re-applying a
    # delta put re-stores the same content-addressed blobs
    "has_chunks", "put_objects_delta",
    "size_object", "stat_object", "touch_objects", "get_ref", "set_ref",
    "delete_ref", "list_refs",
    # gc_mark re-marks from scratch on retry (the superseded mark is
    # discarded server-side); gc_sweep is NOT retryable — a sweep whose
    # reply was lost consumed its mark, and a blind re-send would race
    # whatever uploads happened since
    "gc_mark",
})

#: non-idempotent ref updates: a transport fault after the request may have
#: been delivered leaves the ref state UNKNOWN — surfaced as
#: :class:`AmbiguousRefUpdate`, never as a plain failure (a "failed" push
#: could otherwise have silently succeeded; see docs/remote_store.md)
_AMBIGUOUS_OPS = frozenset({"cas_ref", "cas_refs"})


class RemoteStore:
    """StoreBackend client over a transport — a drop-in store replacement.

    >>> remote = RemoteStore(LoopbackTransport(RemoteServer(ObjectStore(p))))
    >>> remote.put(b"blob")  # content-addressed PUT over the wire

    ``allow_delete`` gates :meth:`delete_object`: remote objects are
    immutable to ordinary clients (a tier-mounted lake must never collect
    from the shared remote); only an explicit remote-side GC run
    (``repro gc --remote``) opens the sweep path.
    """

    def __init__(self, transport, *, retries: int = 2,
                 allow_delete: bool = False):
        self.transport = transport
        self.retries = retries
        self.allow_delete = allow_delete
        #: None = unknown, False = server predates the encoded wire ops
        self._encoded_ops: Optional[bool] = None
        #: None = unknown, False = server predates the delta wire ops
        self._delta_ops: Optional[bool] = None

    # ------------------------------------------------------------ plumbing
    def _call(self, op: str, **kwargs) -> Dict[str, Any]:
        request = {"op": op, **kwargs}
        payload = _pack(request)
        attempts = 1 + (self.retries if op in _RETRYABLE_OPS else 0)
        last: Optional[Exception] = None
        for _ in range(attempts):
            try:
                reply = _unpack(self.transport.request(payload))
                break
            except RemoteError as e:
                last = e
        else:
            if op in _AMBIGUOUS_OPS:
                raise AmbiguousRefUpdate(
                    f"{op}: transport failed after the update may have "
                    "been delivered; remote ref state is unknown — "
                    "re-read to resolve") from last
            raise RemoteError(f"{op}: transport failed after "
                              f"{attempts} attempts") from last
        if not isinstance(reply, dict):
            raise RemoteError(f"{op}: malformed reply from server "
                              f"({type(reply).__name__})")
        err = reply.get("error")
        if err:
            msg = reply.get("message", "")
            if err == "object_not_found":
                raise ObjectNotFound(msg)
            if err == "ref_not_found":
                raise RefNotFound(msg)
            if err == "ref_conflict":
                raise RefConflict(msg)
            if err == "codec_unavailable":
                raise CodecUnavailable(msg)
            raise RemoteError(f"{op}: {err}: {msg}")
        return reply

    def close(self) -> None:
        self.transport.close()

    # ------------------------------------------------------------- objects
    def put(self, data: bytes) -> str:
        digest = sha256_hex(data)
        return self._call("put_object", digest=digest, data=data)["digest"]

    def put_parts(self, parts: Sequence[bytes]) -> str:
        # the wire frame carries one ``bytes`` value
        return self.put(b"".join(parts))

    def get(self, digest: str) -> bytes:
        data = self._call("get_object", digest=digest)["data"]
        if sha256_hex(data) != digest:  # never trust the wire
            raise ObjectNotFound(f"digest mismatch for {digest} from remote")
        return data

    def has(self, digest: str) -> bool:
        return bool(self.has_many([digest]))

    def has_many(self, digests: Iterable[str]) -> Set[str]:
        digests = list(digests)
        if not digests:
            return set()
        return set(self._call("head_objects", digests=digests)["present"])

    def get_many(self, digests: Sequence[str]) -> Dict[str, bytes]:
        digests = list(digests)
        if not digests:
            return {}
        reply = self._call("get_objects", digests=digests)
        out: Dict[str, bytes] = {}
        for digest, data in reply["objects"]:
            if sha256_hex(data) != digest:  # never trust the wire
                raise ObjectNotFound(
                    f"digest mismatch for {digest} from remote")
            out[digest] = data
        missing = [d for d in digests if d not in out]
        if missing:
            raise ObjectNotFound(
                f"remote returned {len(out)}/{len(digests)} objects "
                f"(first missing: {missing[0]})")
        return out

    def put_many(self, blobs: Sequence[bytes]) -> List[str]:
        blobs = list(blobs)
        if not blobs:
            return []
        items = [[sha256_hex(b), b] for b in blobs]
        digests = list(self._call("put_objects", objects=items)["digests"])
        if digests != [d for d, _b in items]:
            raise RemoteError("put_objects: server acknowledged different "
                              "digests than were sent")
        return digests

    def size(self, digest: str) -> int:
        return self._call("size_object", digest=digest)["size"]

    def mtime(self, digest: str) -> float:
        """Upload mtime over the wire (``stat_object``).  Raises
        :class:`RemoteError` ("unknown op") against a server predating the
        op — the GC sweep treats that as "no age data" and degrades, with
        a warning, to the legacy sweep-everything behavior."""
        return self.stat(digest)[1]

    def stat(self, digest: str) -> Tuple[int, float]:
        """``(size, mtime)`` in one ``stat_object`` round-trip — the
        per-candidate cost of a client-side grace-window sweep."""
        reply = self._call("stat_object", digest=digest)
        return int(reply["size"]), float(reply["mtime"])

    def delete_object(self, digest: str) -> bool:
        if not self.allow_delete:
            raise RemoteError(
                "remote objects are immutable to this client; open the "
                "remote with allow_delete=True (repro gc --remote) to "
                "run a remote-side sweep")
        return bool(self._call("delete_object", digest=digest)["deleted"])

    def touch_many(self, digests: Sequence[str]) -> int:
        """Batched remote mtime refresh (sync touch-on-dedup).  Best
        effort by contract: a server predating ``touch_objects`` answers
        "unknown op" and this degrades to 0 touched — the GC generation
        token still protects such pushes, just via retry instead."""
        digests = list(digests)
        if not digests:
            return 0
        try:
            return int(self._call("touch_objects",
                                  digests=digests)["touched"])
        except RemoteError as e:
            if self._is_unknown_op(e):
                return 0
            raise

    # ------------------------------------------------------ server-side GC
    def gc_mark(self, *, drop_cache: bool = False,
                dry_run: bool = False) -> Tuple[str, int]:
        """Run the GC mark phase ON the server (its own refs, its own
        store — zero per-object wire reads).  Returns ``(generation,
        live_count)``; hand the token to :meth:`gc_sweep`.  Gated on
        ``allow_delete`` like the sweep itself: marking bumps the shared
        generation token, which fails concurrent pushes' ref updates —
        not something a read-only tier client should be able to do.
        Dry runs neither bump nor delete, so they need no opt-in."""
        if not self.allow_delete and not dry_run:
            raise RemoteError(
                "remote GC requires a client opened with allow_delete="
                "True (repro gc --remote)")
        reply = self._call("gc_mark", drop_cache=drop_cache,
                           dry_run=dry_run)
        return str(reply["generation"]), int(reply["live"])

    def gc_sweep(self, generation: str, *, prune_age: float = 0.0,
                 dry_run: bool = False) -> Tuple[int, int, int]:
        """Sweep server-side under a mark token from :meth:`gc_mark`.
        Returns ``(swept, bytes_freed, skipped_young)``."""
        if not self.allow_delete and not dry_run:
            raise RemoteError(
                "remote GC requires a client opened with allow_delete="
                "True (repro gc --remote)")
        reply = self._call("gc_sweep", generation=generation,
                           prune_age=prune_age, dry_run=dry_run)
        return (int(reply["swept"]), int(reply["bytes_freed"]),
                int(reply["skipped_young"]))

    # -------------------------------------------------- encoded payloads
    def _supports_encoded(self) -> bool:
        return self._encoded_ops is not False

    @staticmethod
    def _is_unknown_op(e: RemoteError) -> bool:
        return "bad_request" in str(e) and "unknown op" in str(e)

    def get_encoded(self, digest: str) -> bytes:
        return self.get_many_encoded([digest])[digest]

    def put_encoded(self, payload: bytes) -> str:
        return self.put_many_encoded([payload])[0]

    def _encoded_unsupported(self, e: Optional[RemoteError] = None):
        """A server predating the encoded wire ops answers "unknown op":
        remember that and surface :class:`CodecUnavailable`, the same
        signal a codec mismatch sends — callers (the transfer engine)
        respond identically by re-sending raw, and the accounting then
        reflects the raw bytes that actually crossed the wire."""
        self._encoded_ops = False
        raise CodecUnavailable(
            "server predates the encoded wire ops") from e

    def get_many_encoded(self, digests: Sequence[str]) -> Dict[str, bytes]:
        """Batched fetch of framed payloads (compressed wire frames).
        The caller decodes + digest-verifies (``decode_frame``)."""
        digests = list(digests)
        if not digests:
            return {}
        if not self._supports_encoded():
            self._encoded_unsupported()
        try:
            reply = self._call("get_objects_encoded", digests=digests)
        except RemoteError as e:
            if self._is_unknown_op(e):
                self._encoded_unsupported(e)
            raise
        out = {d: payload for d, payload in reply["objects"]}
        missing = [d for d in digests if d not in out]
        if missing:
            raise ObjectNotFound(
                f"remote returned {len(out)}/{len(digests)} encoded "
                f"objects (first missing: {missing[0]})")
        return out

    def put_many_encoded(self, payloads: Sequence[bytes],
                         digests: Optional[Sequence[str]] = None
                         ) -> List[str]:
        payloads = list(payloads)
        if not payloads:
            return []
        if not self._supports_encoded():
            self._encoded_unsupported()
        if digests is not None and len(digests) == len(payloads):
            # caller already decoded + verified (the transfer engine does);
            # the server decodes and re-verifies every payload regardless,
            # so skipping the redundant local decode loses no checking
            items = [[d, p] for d, p in zip(digests, payloads)]
        else:
            items = [[sha256_hex(decode_frame(p, what="encoded payload")), p]
                     for p in payloads]
        try:
            reply = self._call("put_objects_encoded", objects=items)
        except RemoteError as e:
            if self._is_unknown_op(e):
                self._encoded_unsupported(e)
            raise
        digests = list(reply["digests"])
        if digests != [d for d, _p in items]:
            raise RemoteError(
                "put_objects_encoded: server acknowledged different "
                "digests than were sent")
        return digests

    # ------------------------------------------------------- delta frames
    def _supports_delta(self) -> bool:
        """False once the server has answered "unknown op" for a delta op.
        Unlike the encoded-payload downgrade (which must redo the transfer
        raw, so it raises), delta degrades SILENTLY: a whole-frame put was
        always going to happen anyway, the delta path only tries to shrink
        it first."""
        return self._delta_ops is not False

    def has_chunks(self, hashes: Sequence[str]) -> Set[str]:
        """Which content-defined chunk hashes the server can resolve.
        Empty set against an old server (after one "unknown op" probe) —
        the sender then finds nothing to reference and ships whole frames,
        which is exactly the downgrade semantics we want."""
        hashes = list(hashes)
        if not hashes or not self._supports_delta():
            return set()
        try:
            reply = self._call("has_chunks", hashes=hashes)
        except RemoteError as e:
            if self._is_unknown_op(e):
                self._delta_ops = False
                return set()
            raise
        self._delta_ops = True
        return set(reply["present"])

    def put_objects_delta(self, items: Sequence[Tuple[str, list]]
                          ) -> Tuple[List[str], List[str]]:
        """Batched delta put → ``(stored digests, stale digests)``.

        Stale = the server could no longer resolve a referenced chunk
        (index eviction / concurrent GC); the caller re-sends those blobs
        whole-frame.  Against an old server every blob is reported stale —
        same re-send path, no special casing."""
        items = list(items)
        if not items:
            return [], []
        if not self._supports_delta():
            return [], [d for d, _r in items]
        try:
            reply = self._call("put_objects_delta",
                               objects=[[d, r] for d, r in items])
        except RemoteError as e:
            if self._is_unknown_op(e):
                self._delta_ops = False
                return [], [d for d, _r in items]
            raise
        stored = list(reply["digests"])
        stale = list(reply.get("stale") or [])
        sent = {d for d, _r in items}
        if not set(stored) | set(stale) >= sent:
            raise RemoteError(
                "put_objects_delta: server reply does not account for "
                "every blob sent")
        return stored, stale

    def list_objects(self, *, page_token: Optional[str] = None,
                     limit: int = 1000
                     ) -> Tuple[List[str], Optional[str]]:
        reply = self._call("list_objects", token=page_token or "",
                           limit=limit)
        return list(reply["digests"]), reply.get("next") or None

    def iter_objects(self) -> Iterator[str]:
        token: Optional[str] = None
        while True:
            page, token = self.list_objects(page_token=token)
            yield from page
            if token is None:
                return

    # ---------------------------------------------------------------- refs
    def set_ref(self, name: str, digest: str) -> None:
        self._call("set_ref", name=name, digest=digest)

    def get_ref(self, name: str) -> str:
        return self._call("get_ref", name=name)["digest"]

    def cas_ref(self, name: str, expected: Optional[str], new: str) -> None:
        self._call("cas_ref", name=name,
                   expected=_ABSENT if expected is None else expected,
                   new=new)

    def cas_refs(self, updates: Sequence[Tuple[str, Optional[str], str]]
                 ) -> None:
        self._call("cas_refs", updates=[
            [name, _ABSENT if expected is None else expected, new]
            for name, expected, new in updates])

    def delete_ref(self, name: str) -> None:
        self._call("delete_ref", name=name)

    def list_refs(self, prefix: str = "", *,
                  page_token: Optional[str] = None, limit: int = 1000
                  ) -> Tuple[List[Tuple[str, str]], Optional[str]]:
        reply = self._call("list_refs", prefix=prefix,
                           token=page_token or "", limit=limit)
        return [(n, d) for n, d in reply["refs"]], reply.get("next") or None

    def iter_refs(self, prefix: str = "") -> Iterator[str]:
        token: Optional[str] = None
        while True:
            page, token = self.list_refs(prefix, page_token=token)
            for name, _digest in page:
                yield name
            if token is None:
                return


# -------------------------------------------------------------------- tiering
class TieredStore:
    """local→remote read-through with local write-back.

    * ``get``: local hit, else fetch from the remote, persist locally
      (write-back), return — so a blob is paid for once per host;
    * ``has``/``has_many``: local first, remainder asked remotely;
    * refs: read local-first with remote fallback (a run-cache key that only
      host A has still hits on host B); every write lands locally only —
      publishing to the remote is an explicit ``push``, never a side effect;
    * enumeration (``iter_objects``/``list_objects``/``delete_object``) is
      local-tier only: GC sweeps the cache tier, never the shared remote.
    """

    def __init__(self, local: ObjectStore, remote: StoreBackend):
        self.local = local
        self.remote = remote

    @property
    def root(self):
        return self.local.root

    # ------------------------------------------------------------- objects
    def put(self, data: bytes) -> str:
        return self.local.put(data)

    def put_parts(self, parts: Sequence[bytes]) -> str:
        return self.local.put_parts(parts)

    def get(self, digest: str) -> bytes:
        try:
            return self.local.get(digest)
        except ObjectNotFound:
            data = self.remote.get(digest)
            self.local.put(data)  # write-back: next read is local
            return data

    def has(self, digest: str) -> bool:
        return self.local.has(digest) or self.remote.has(digest)

    def has_many(self, digests: Iterable[str]) -> Set[str]:
        digests = list(digests)
        present = self.local.has_many(digests)
        rest = [d for d in digests if d not in present]
        if rest:
            present |= self.remote.has_many(rest)
        return present

    def get_many(self, digests: Sequence[str]) -> Dict[str, bytes]:
        out: Dict[str, bytes] = {}
        rest: List[str] = []
        for d in digests:
            try:
                out[d] = self.local.get(d)
            except ObjectNotFound:
                rest.append(d)
        if rest:
            fetched = self.remote.get_many(rest)
            for d, data in fetched.items():
                self.local.put(data)  # write-back, same as single get
                out[d] = data
        return out

    def put_many(self, blobs: Sequence[bytes]) -> List[str]:
        return self.local.put_many(blobs)

    def size(self, digest: str) -> int:
        try:
            return self.local.size(digest)
        except ObjectNotFound:
            return self.remote.size(digest)

    def mtime(self, digest: str) -> float:
        try:
            return self.local.mtime(digest)
        except ObjectNotFound:
            return self.remote.mtime(digest)

    def stat(self, digest: str) -> Tuple[int, float]:
        try:
            return self.local.stat(digest)
        except ObjectNotFound:
            return self.remote.stat(digest)

    def delete_object(self, digest: str) -> bool:
        return self.local.delete_object(digest)

    def touch_many(self, digests: Sequence[str]) -> int:
        # writes land locally, so the local tier is what a local GC would
        # sweep — touch there; never mutate the shared remote's clocks
        # from a tier mount
        return self.local.touch_many(list(digests))

    # -------------------------------------------------- encoded payloads
    def _supports_encoded(self) -> bool:
        """Forward the mounted remote's capability, so the transfer
        engine's encoded-path kill switch sees through the tier."""
        supports = getattr(self.remote, "_supports_encoded", None)
        return True if supports is None else supports()

    def get_encoded(self, digest: str) -> bytes:
        try:
            return self.local.get_encoded(digest)
        except ObjectNotFound:
            payload = self.remote.get_encoded(digest)
            self.local.put_encoded(payload)  # write-back, compressed form
            return payload

    def put_encoded(self, payload: bytes) -> str:
        return self.local.put_encoded(payload)

    def get_many_encoded(self, digests: Sequence[str]) -> Dict[str, bytes]:
        out: Dict[str, bytes] = {}
        rest: List[str] = []
        for d in digests:
            try:
                out[d] = self.local.get_encoded(d)
            except ObjectNotFound:
                rest.append(d)
        if rest:
            fetched = self.remote.get_many_encoded(rest)
            for d, payload in fetched.items():
                self.local.put_encoded(payload)
                out[d] = payload
        return out

    def put_many_encoded(self, payloads: Sequence[bytes],
                         digests: Optional[Sequence[str]] = None
                         ) -> List[str]:
        return self.local.put_many_encoded(payloads, digests=digests)

    def iter_objects(self) -> Iterator[str]:
        return self.local.iter_objects()

    def list_objects(self, *, page_token: Optional[str] = None,
                     limit: int = 1000):
        return self.local.list_objects(page_token=page_token, limit=limit)

    # ---------------------------------------------------------------- refs
    def set_ref(self, name: str, digest: str) -> None:
        self.local.set_ref(name, digest)

    def get_ref(self, name: str) -> str:
        try:
            return self.local.get_ref(name)
        except RefNotFound:
            return self.remote.get_ref(name)

    def cas_ref(self, name: str, expected: Optional[str], new: str) -> None:
        # CAS against the *tiered* view (a branch head may only exist
        # remotely yet) but always write locally — under the local store's
        # cross-process ref guard, so two processes sharing one lake
        # directory cannot both win (same linearizability as the plain
        # ObjectStore.cas_ref).
        with self.local.ref_guard():
            try:
                current: Optional[str] = self.get_ref(name)
            except RefNotFound:
                current = None
            if current != expected:
                raise RefConflict(
                    f"ref {name}: expected {expected!r}, found {current!r}")
            self.local.set_ref(name, new)

    def cas_refs(self, updates: Sequence[Tuple[str, Optional[str], str]]
                 ) -> None:
        # validate every expectation against the *tiered* view, apply every
        # write locally — all inside the local store's cross-process guard,
        # so the batch is all-or-nothing exactly like ObjectStore.cas_refs
        with self.local.ref_guard():
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
                self.local.set_ref(name, new)

    def delete_ref(self, name: str) -> None:
        self.local.delete_ref(name)

    def iter_refs(self, prefix: str = "") -> Iterator[str]:
        names = set(self.local.iter_refs(prefix))
        try:
            names.update(self.remote.iter_refs(prefix))
        except ReproError:  # unreachable remote: degrade to the local tier
            pass
        yield from sorted(names)

    def list_refs(self, prefix: str = "", *,
                  page_token: Optional[str] = None, limit: int = 1000
                  ) -> Tuple[List[Tuple[str, str]], Optional[str]]:
        limit = max(1, limit)
        page: List[Tuple[str, str]] = []
        last: Optional[str] = None
        for name in self.iter_refs(prefix):
            if page_token is not None and name <= page_token:
                continue
            try:
                page.append((name, self.get_ref(name)))
            except RefNotFound:
                continue
            last = name
            if len(page) >= limit:
                return page, last
        return page, None


# ----------------------------------------------------------------- connectors
def connect(url_or_path: str, *, retries: int = 2,
            allow_delete: bool = False) -> StoreBackend:
    """Open a remote store from a URL or a filesystem path:

    * ``http(s)://host:port`` — msgpack wire protocol (``repro serve``);
    * ``s3://host:port/bucket`` — S3-compatible REST dialect
      (:class:`~repro.core.s3.S3Backend`; ``repro serve --s3`` or any
      server speaking the dialect);
    * a path — served through an in-process loopback, so every access
      still exercises the full wire contract.

    ``allow_delete`` opens the remote-side GC sweep path
    (``repro gc --remote``); S3 backends are direct object-store clients,
    so the flag only gates the msgpack protocol's ``delete_object`` op."""
    if url_or_path.startswith("s3://"):
        from .s3 import S3Backend

        return S3Backend.from_url(url_or_path, retries=retries)
    if url_or_path.startswith(("http://", "https://")):
        return RemoteStore(HTTPTransport(url_or_path), retries=retries,
                           allow_delete=allow_delete)
    path = url_or_path[len("file://"):] if url_or_path.startswith("file://") \
        else url_or_path
    return RemoteStore(LoopbackTransport(RemoteServer(ObjectStore(path))),
                       retries=retries, allow_delete=allow_delete)
