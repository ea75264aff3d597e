"""Iceberg-style tables: a three-level metadata hierarchy over tensor files.

Fig. 2, layer 3 — but with the real Iceberg shape instead of a flat file
list.  A table snapshot is the root of a content-addressed tree:

    snapshot blob        { v:1, schema, manifest_list: <digest>,
                           parent, op, seq, nrows, nbytes }
    manifest-list blob   { v:1, manifests: [[digest, nrows, nbytes,
                           nfiles, zone], ...] }
    manifest blob        { v:1, entries: [[digest, nrows, nbytes,
                           stats], ...] }
    tensorfile blobs     the data files themselves

Every level is immutable and content addressed, so the hierarchy dedups in
the store: an **append writes O(delta) metadata** — one new manifest blob
for the new files plus a small manifest-list and snapshot blob — and reuses
every parent manifest *verbatim* (same digest, no copy, no re-upload on
push).  Each manifest-list row carries a **zone map** (per-column min/max/
null-count rolled up from the per-file stats), so a predicate scan prunes
whole manifests with one comparison before it prunes files, and never
fetches a data blob that provably contains no matching row.

Row order is part of the table's logical contents: manifests in list
order, entries in manifest order, rows in file order.  That makes
:meth:`TableIO.logical_digest` well-defined — the fingerprint compaction
uses to *prove* a rewrite lossless (``core/compact.py``).

Legacy format (v0, pre-hierarchy) stored the flat entry list inline in the
snapshot blob under ``"manifest"`` with no ``"v"`` key.  The decoder still
reads those: they surface as a single inline :class:`ManifestFile`, and the
first append on top of one materializes it as a real manifest blob — the
migration path is "touch the table".
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass, field
from typing import (Any, Dict, Iterable, Iterator, List, Mapping, Optional,
                    Sequence, Set, Tuple)

import msgpack
import numpy as np

from . import frame as _frame
from . import tensorfile
from .errors import SchemaError
from .frame import Expr
from .store import ObjectStore
from .tensorfile import Schema

_SNAPSHOT_VERSION = 1


def _pack(obj) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def _unpack(blob: bytes):
    return msgpack.unpackb(blob, raw=False)


@dataclass(frozen=True)
class ManifestEntry:
    """One data file: tensorfile digest + row/byte counts + column stats."""

    digest: str
    nrows: int
    nbytes: int
    stats: Dict[str, Any] = field(default_factory=dict)

    def to_obj(self):
        return [self.digest, self.nrows, self.nbytes, self.stats]

    @staticmethod
    def from_obj(o):
        return ManifestEntry(o[0], o[1], o[2], o[3])


@dataclass(frozen=True)
class ManifestFile:
    """One manifest: a content-addressed batch of data files plus the
    zone-map rollup that lets a scan skip the whole batch in one check.

    ``digest`` is the manifest blob's content address (None until the
    snapshot is stored, or for a legacy-v0 inline manifest that was never
    materialized).  ``entries`` is the inline entry tuple when it is
    already in memory — freshly written manifests and legacy decodes carry
    it; manifests loaded from a manifest-list don't, and are fetched
    lazily via :meth:`TableIO.manifest_entries`."""

    digest: Optional[str]
    nrows: int
    nbytes: int
    nfiles: int
    zone: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    entries: Optional[tuple] = None

    def key(self):
        """Identity for manifest-diffing (``txn.rebase_append``): the blob
        digest when stored, else the ordered data-file digests."""
        if self.digest is not None:
            return self.digest
        return tuple(e.digest for e in (self.entries or ()))


@dataclass(frozen=True)
class Snapshot:
    schema: Schema
    manifests: tuple  # tuple[ManifestFile], scan order
    parent: Optional[str]
    op: str  # "overwrite" | "append" | "compact"
    seq: int

    @property
    def nrows(self) -> int:
        return sum(m.nrows for m in self.manifests)

    @property
    def nbytes(self) -> int:
        return sum(m.nbytes for m in self.manifests)

    @property
    def nfiles(self) -> int:
        return sum(m.nfiles for m in self.manifests)


# ------------------------------------------------------------ manifest blobs
def pack_manifest(entries: Sequence[ManifestEntry]) -> bytes:
    return _pack({"v": 1, "kind": "manifest",
                  "entries": [e.to_obj() for e in entries]})


def unpack_manifest(blob: bytes) -> Tuple[ManifestEntry, ...]:
    obj = _unpack(blob)
    if obj.get("kind") != "manifest":
        raise SchemaError(f"not a manifest blob (kind={obj.get('kind')!r})")
    return tuple(ManifestEntry.from_obj(e) for e in obj["entries"])


def zone_of(entries: Iterable[ManifestEntry]) -> Dict[str, Dict[str, Any]]:
    """Roll per-file column stats up into one zone map.

    A column appears in the zone only when *every* entry has stats for it
    (an entry with empty stats — non-numeric or zero-size column — makes
    the column unknown, so the scan conservatively keeps the manifest).
    ``min``/``max`` bound the non-null values across all entries; they are
    omitted when no entry has any (an all-NaN column).  ``null_count``
    sums the per-file NaN counts (integer columns have none)."""
    entries = list(entries)
    if not entries:
        return {}
    names = set(entries[0].stats)
    for e in entries[1:]:
        names &= set(e.stats)
    zone: Dict[str, Dict[str, Any]] = {}
    for name in sorted(names):
        mins, maxs, nulls, known = [], [], 0, True
        for e in entries:
            st = e.stats.get(name)
            if not st:  # empty stats: pruning on this column is unsound
                known = False
                break
            nulls += int(st.get("nan_count", 0))
            if "min" in st:
                mins.append(st["min"])
                maxs.append(st["max"])
        if not known:
            continue
        info: Dict[str, Any] = {"null_count": nulls}
        if mins:
            info["min"] = min(mins)
            info["max"] = max(maxs)
        zone[name] = info
    return zone


def inline_manifest(entries: Tuple[ManifestEntry, ...]) -> ManifestFile:
    """A not-yet-stored manifest carrying its entries inline."""
    return ManifestFile(
        digest=None,
        nrows=sum(e.nrows for e in entries),
        nbytes=sum(e.nbytes for e in entries),
        nfiles=len(entries),
        zone=zone_of(entries),
        entries=entries,
    )


# -------------------------------------------------- zone-map predicate logic
_CMP_OPS = frozenset({"gt", "ge", "lt", "le", "eq", "ne"})
_MIRROR = {"gt": "lt", "lt": "gt", "ge": "le", "le": "ge",
           "eq": "eq", "ne": "ne"}


def expr_columns(e: Optional[Expr]) -> Set[str]:
    """Column names a predicate reads — what a projected scan must decode
    beyond the requested columns to evaluate the row filter."""
    if e is None:
        return set()
    if e.op == "col":
        return {e.args[0]}
    if e.op == "lit":
        return set()
    out: Set[str] = set()
    for a in e.args:
        if isinstance(a, Expr):
            out |= expr_columns(a)
    return out


def zone_may_match(e: Expr, zone: Mapping[str, Mapping[str, Any]],
                   nrows: int) -> bool:
    """False only when the zone map PROVES no row satisfies ``e`` — the
    pruning test.  Sound by construction: every judgment is a tri-state
    over-approximation ``(may_true, may_false)``, and anything the zone
    cannot bound (arithmetic, col-vs-col, non-numeric columns) collapses
    to (True, True), i.e. "cannot prune".  NumPy NaN semantics are
    honored: a NaN row compares False under every operator except ``!=``,
    which compares True."""
    return _zone_eval(e, zone, nrows)[0]


def _zone_eval(e: Expr, zone, nrows: int) -> Tuple[bool, bool]:
    if nrows == 0:
        return (False, False)
    if e.op == "not":
        mt, mf = _zone_eval(e.args[0], zone, nrows)
        return (mf, mt)
    if e.op == "and":
        a = _zone_eval(e.args[0], zone, nrows)
        b = _zone_eval(e.args[1], zone, nrows)
        return (a[0] and b[0], a[1] or b[1])
    if e.op == "or":
        a = _zone_eval(e.args[0], zone, nrows)
        b = _zone_eval(e.args[1], zone, nrows)
        return (a[0] or b[0], a[1] and b[1])
    if e.op in _CMP_OPS:
        return _zone_cmp(e.op, e.args[0], e.args[1], zone, nrows)
    return (True, True)


def _zone_cmp(op: str, lhs: Expr, rhs: Expr, zone, nrows: int
              ) -> Tuple[bool, bool]:
    if lhs.op == "lit" and rhs.op == "col":
        lhs, rhs, op = rhs, lhs, _MIRROR[op]
    if lhs.op != "col" or rhs.op != "lit":
        return (True, True)
    info = zone.get(lhs.args[0])
    value = rhs.args[0]
    if isinstance(value, (np.integer, np.floating, np.bool_)):
        value = value.item()
    if info is None or not isinstance(value, (bool, int, float)):
        return (True, True)
    # int/float comparisons below are exact in Python (no precision-losing
    # cast), so int64 bounds near 2**63 prune correctly
    nulls = int(info.get("null_count", 0))
    has_range = "min" in info  # paired with "max" by construction
    lo, hi = info.get("min"), info.get("max")
    if isinstance(value, float) and math.isnan(value):
        # NaN literal: every comparison is False except !=, which is True
        if op == "ne":
            return (True, False)
        return (False, True)
    if not has_range:  # all rows NaN: only != matches
        if op == "ne":
            return (True, False)
        return (False, True)
    if op == "eq":
        return (lo <= value <= hi,
                nulls > 0 or lo != value or hi != value)
    if op == "ne":
        return (nulls > 0 or lo != value or hi != value,
                lo <= value <= hi)
    if op == "gt":
        return (hi > value, nulls > 0 or lo <= value)
    if op == "ge":
        return (hi >= value, nulls > 0 or lo < value)
    if op == "lt":
        return (lo < value, nulls > 0 or hi >= value)
    return (lo <= value, nulls > 0 or hi > value)  # le


class TableIO:
    """Write/read path between in-memory columns and snapshots.

    write: columns → tensorfile blobs → manifest → manifest-list → snapshot
    read:  snapshot digest → manifest-list → (zone-pruned) manifests →
           (stat-pruned) tensorfile blobs → columns
    (the reversible hierarchy of Fig. 2, now three metadata levels deep).
    """

    def __init__(self, store: ObjectStore, *, target_rows_per_file: int = 65536,
                 on_read=None):
        self.store = store
        self.target_rows_per_file = target_rows_per_file
        #: optional callback fired with each snapshot digest a read touches
        #: — the read-set capture hook transactions use (``core/txn.py``):
        #: a pipeline node that only holds the IO handle still contributes
        #: the tables it reads to its transaction's declared set
        self.on_read = on_read

    def with_read_recorder(self, on_read) -> "TableIO":
        """A sibling handle over the same store whose reads fire
        ``on_read(snapshot_digest)`` (this handle is left untouched)."""
        return TableIO(self.store,
                       target_rows_per_file=self.target_rows_per_file,
                       on_read=on_read)

    # ------------------------------------------------------------------ write
    def write_snapshot(
        self,
        cols: Mapping[str, np.ndarray],
        *,
        parent: Optional[str] = None,
        op: str = "overwrite",
    ) -> str:
        """Persist columns as a new snapshot; returns the snapshot digest.

        ``op="append"`` is O(delta): the parent's manifests are reused
        *verbatim* (same blobs, same digests — the store dedups them) and
        the new rows land as exactly one new manifest, however many files
        they chunk into."""
        schema: Optional[Schema] = None
        seq = 0
        parent_manifests: tuple = ()
        if parent is not None:
            parent_snap = self.load_snapshot(parent)
            seq = parent_snap.seq + 1
            if op == "append":
                parent_manifests = parent_snap.manifests
                schema = parent_snap.schema

        entries: List[ManifestEntry] = []
        for chunk in _row_chunks(cols, self.target_rows_per_file):
            parts, meta = tensorfile.encode_parts(chunk)
            digest = self.store.put_parts(parts)
            chunk_schema = Schema.from_obj(meta["schema"])
            if schema is None:
                schema = chunk_schema
            else:
                schema.check_compatible(chunk_schema)
            entries.append(
                ManifestEntry(digest, meta["nrows"], meta["nbytes"], meta["stats"])
            )
        if schema is None:
            raise SchemaError("empty snapshot")
        manifests = parent_manifests + (inline_manifest(tuple(entries)),)
        snap = Snapshot(schema, manifests, parent, op, seq)
        return self.store_snapshot(snap)

    def append(self, parent: str, cols: Mapping[str, np.ndarray]) -> str:
        return self.write_snapshot(cols, parent=parent, op="append")

    def append_stream(self, parent: Optional[str],
                      batches: Iterable[Mapping[str, np.ndarray]]) -> str:
        """Micro-batch ingestion: land each batch as one append snapshot
        chained on the previous (``parent=None`` starts the table with the
        first batch).  Each step costs O(batch) data + O(delta) metadata,
        so sustained ingest rate is flat in table size; run
        ``core/compact.py`` behind the stream to fold the small fragments
        back into ``target_rows_per_file``-sized files.  Returns the final
        snapshot digest."""
        head = parent
        for batch in batches:
            head = (self.write_snapshot(batch) if head is None
                    else self.append(head, batch))
        if head is None:
            raise SchemaError("append_stream: no batches")
        return head

    def store_snapshot(self, snap: Snapshot) -> str:
        """Persist a :class:`Snapshot` tree: materialize inline manifests
        as content-addressed blobs, then the manifest-list, then the
        snapshot root.  Already-stored manifests are referenced by digest
        — re-putting them is a no-op thanks to content addressing."""
        stored: List[ManifestFile] = []
        for mf in snap.manifests:
            if mf.digest is None:
                digest = self.store.put(pack_manifest(mf.entries or ()))
                mf = ManifestFile(digest, mf.nrows, mf.nbytes, mf.nfiles,
                                  mf.zone, mf.entries)
            stored.append(mf)
        mlist = _pack({
            "v": 1,
            "kind": "manifest_list",
            "manifests": [[m.digest, m.nrows, m.nbytes, m.nfiles, m.zone]
                          for m in stored],
        })
        obj = {
            "v": _SNAPSHOT_VERSION,
            "schema": snap.schema.to_obj(),
            "manifest_list": self.store.put(mlist),
            "parent": snap.parent,
            "op": snap.op,
            "seq": snap.seq,
            "nrows": snap.nrows,
            "nbytes": snap.nbytes,
        }
        return self.store.put(_pack(obj))

    # ------------------------------------------------------------------- read
    def load_snapshot(self, digest: str) -> Snapshot:
        obj = _unpack(self.store.get(digest))
        mlist_digest = obj.get("manifest_list")
        if mlist_digest is not None:  # v1 hierarchy
            mlist = _unpack(self.store.get(mlist_digest))
            manifests = tuple(
                ManifestFile(digest=row[0], nrows=row[1], nbytes=row[2],
                             nfiles=row[3], zone=row[4])
                for row in mlist["manifests"])
        else:  # legacy v0: flat entry list inline in the snapshot blob
            entries = tuple(ManifestEntry.from_obj(e)
                            for e in obj["manifest"])
            manifests = (inline_manifest(entries),) if entries else ()
        return Snapshot(
            schema=Schema.from_obj(obj["schema"]),
            manifests=manifests,
            parent=obj["parent"],
            op=obj["op"],
            seq=obj["seq"],
        )

    def manifest_entries(self, mf: ManifestFile) -> Tuple[ManifestEntry, ...]:
        """The manifest's data-file entries, fetching the blob if they are
        not inline."""
        if mf.entries is not None:
            return mf.entries
        return unpack_manifest(self.store.get(mf.digest))

    def iter_files(self, digest: str,
                   columns: Optional[Sequence[str]] = None,
                   where: Optional[Expr] = None
                   ) -> Iterator[Dict[str, np.ndarray]]:
        """Decoded data files of a snapshot, in row order.

        ``columns`` pushes projection into the tensorfile decode — columns
        outside the selection (plus any the predicate needs) are never
        materialized.  ``where`` prunes at two levels before any data blob
        is fetched: a manifest whose zone map proves no row can match is
        skipped whole (its manifest blob is not even read), then each
        surviving file is re-tested against its own per-file stats.
        Pruning is sound, not exact — callers still apply the row filter
        (:meth:`read` does)."""
        if self.on_read is not None:
            self.on_read(digest)
        snap = self.load_snapshot(digest)
        need: Optional[List[str]] = None
        if columns is not None:
            need = list(dict.fromkeys(
                list(columns) + sorted(expr_columns(where))))
            known = set(snap.schema.names())
            missing = sorted(set(need) - known)
            if missing:
                raise SchemaError(f"missing columns {missing}")
        for mf in snap.manifests:
            if where is not None and not zone_may_match(where, mf.zone,
                                                        mf.nrows):
                continue  # whole manifest pruned: blob never fetched
            for entry in self.manifest_entries(mf):
                if where is not None and not zone_may_match(
                        where, zone_of((entry,)), entry.nrows):
                    continue  # file pruned by its own stats
                yield tensorfile.decode(self.store.get(entry.digest),
                                        columns=need)

    def read(self, digest: str, columns: Optional[Sequence[str]] = None,
             where: Optional[Expr] = None) -> Dict[str, np.ndarray]:
        """Materialize (a projection/selection of) a snapshot.

        Equivalent to decoding everything and filtering in memory — the
        zone-map pruning in :meth:`iter_files` plus the exact row filter
        applied here guarantee it (property-tested in
        tests/test_table_format.py) — but selective predicates skip most
        data blobs entirely."""
        frames = list(self.iter_files(digest, columns=columns, where=where))
        if not frames:  # every fragment pruned: empty, correctly typed
            snap = self.load_snapshot(digest)
            names = list(columns) if columns is not None \
                else snap.schema.names()
            spec = {c.name: c for c in snap.schema.columns}
            missing = sorted(set(names) - set(spec))
            if missing:
                raise SchemaError(f"missing columns {missing}")
            return {n: np.zeros((0, *spec[n].row_shape),
                                dtype=tensorfile.resolve_dtype(spec[n].dtype))
                    for n in names}
        cols = tensorfile.concat(frames)
        if where is not None:
            cols = _frame.where(cols, where)
        if columns is not None:
            missing = set(columns) - cols.keys()
            if missing:
                raise SchemaError(f"missing columns {sorted(missing)}")
            cols = {k: cols[k] for k in columns}
        return cols

    def logical_digest(self, digest: str) -> str:
        """Fingerprint of the table's LOGICAL contents: schema + each
        column's row bytes concatenated in row order, independent of how
        rows are fragmented into files or manifests.  Two snapshots with
        the same logical digest hold bit-identical tables — the proof
        obligation compaction discharges (``core/compact.py``)."""
        snap = self.load_snapshot(digest)
        names = snap.schema.names()
        hashers = {name: hashlib.sha256() for name in names}
        for frame in self.iter_files(digest):
            for name in names:
                hashers[name].update(
                    np.ascontiguousarray(frame[name]).tobytes())
        acc = hashlib.sha256(_pack(snap.schema.to_obj()))
        for name in names:
            acc.update(name.encode("utf-8"))
            acc.update(hashers[name].digest())
        return acc.hexdigest()

    def history(self, digest: str) -> List[str]:
        """Snapshot lineage, newest first (time travel within one table)."""
        out, cur = [], digest
        while cur is not None:
            out.append(cur)
            cur = self.load_snapshot(cur).parent
        return out


def _row_chunks(cols: Mapping[str, np.ndarray], rows_per_file: int):
    arrays = {k: np.asarray(v) for k, v in cols.items()}
    if not arrays:
        raise SchemaError("no columns")
    n = next(iter(arrays.values())).shape[0]
    if n == 0:
        raise SchemaError("empty columns")
    for start in range(0, n, rows_per_file):
        stop = min(start + rows_per_file, n)
        yield {k: v[start:stop] for k, v in arrays.items()}
