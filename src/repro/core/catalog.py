"""Nessie-style catalog: Git semantics over tables (paper §3.3, Fig. 4).

A *commit* is an immutable, content-addressed, multi-table transaction:

    { parents: [digest...], tables: {name: snapshot_digest}, message,
      author, ts, meta }

Branches are mutable refs (name → commit digest) updated with compare-and-set,
which gives the catalog the transactional behavior the paper needs for data
pipelines.  Branching is **copy-on-write**: creating a branch writes one ref —
no table data is copied regardless of size (benchmarked in
``benchmarks/bench_branching.py``).

Namespacing follows the paper's ``user.branch`` convention: everyone can read
any branch, only ``user`` can write ``user.*``; ``main`` accepts only merges
that went through write-audit-publish (see ``wap.py``) unless the catalog is
created with ``protect_main=False``.

Writes are **optimistic table-level transactions** (``txn.py``): a commit
declares its read/write table set, and a ref-level CAS miss triggers a
rebase — re-read the moved head, verify no declared table changed
snapshot since the transaction's base, retry — so concurrent writers on
*disjoint* tables never see a conflict; only genuinely overlapping
snapshot movement raises :class:`~.errors.TransactionConflict`.  **Data
contracts** (``contracts.py``) attached to tables ride the commit object
itself and are enforced here, at the ref update, on every ``commit`` and
``merge`` path — see docs/catalog.md for the conflict matrix and
enforcement points.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Set

import msgpack

from .. import tracing
from .contracts import (CONTRACTS_TABLE, Contract, Rule, evaluate,
                        pack_contracts, unpack_contracts)
from .errors import (ContractViolation, MergeConflict, ObjectNotFound,
                     PermissionDenied, RefConflict, RefNotFound, ReproError,
                     TransactionConflict)
from .store import ObjectStore, try_cas_ref
from .txn import DEFAULT_MAX_ATTEMPTS, Transaction, changed_tables

_BRANCH_PREFIX = "branch="
_TAG_PREFIX = "tag="
#: namespace for remote-tracking refs: ``remote/<name>/branch=<b>`` (and
#: ``remote/<name>/tag=<t>`` for synced tags) records where the ref pointed
#: on remote ``<name>`` at the last push/pull.  These are GC roots (see
#: ``gc.collect``) — objects reachable only through a remote-tracking ref
#: must survive a local sweep or the next replay of a pulled branch/tag
#: would break.
REMOTE_REF_PREFIX = "remote/"


def remote_tracking_ref(remote_name: str, branch: str) -> str:
    return f"{REMOTE_REF_PREFIX}{remote_name}/{_BRANCH_PREFIX}{branch}"


def remote_tracking_tag_ref(remote_name: str, tag: str) -> str:
    return f"{REMOTE_REF_PREFIX}{remote_name}/{_TAG_PREFIX}{tag}"


def _pack(obj) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def _unpack(blob: bytes):
    return msgpack.unpackb(blob, raw=False)


@dataclass(frozen=True)
class Commit:
    parents: tuple
    tables: Dict[str, str]  # table name -> snapshot digest
    message: str
    author: str
    ts: float
    meta: Dict[str, Any] = field(default_factory=dict)

    def to_obj(self):
        return {
            "parents": list(self.parents),
            "tables": dict(sorted(self.tables.items())),
            "message": self.message,
            "author": self.author,
            "ts": self.ts,
            "meta": self.meta,
        }

    @staticmethod
    def from_obj(o) -> "Commit":
        return Commit(tuple(o["parents"]), dict(o["tables"]), o["message"],
                      o["author"], o["ts"], o.get("meta", {}))


class Catalog:
    def __init__(self, store: ObjectStore, *, protect_main: bool = True,
                 clock: Callable[[], float] = time.time):
        self.store = store
        self.protect_main = protect_main
        self.clock = clock
        self._io = None  # lazy TableIO for contract enforcement
        self._contracts_cache: Dict[str, Dict[str, Contract]] = {}
        #: (contracts digest, table, snapshot) -> failures; rebases re-check
        #: the same snapshot under the same contracts for free
        self._contract_results: Dict[tuple, Dict[str, str]] = {}
        self._stats_lock = threading.Lock()
        #: transaction accounting: ``rebases`` counts ref-CAS misses
        #: absorbed internally — before the transaction layer each one was
        #: a caller-visible conflict and a full retry (bench_branching's
        #: multi-writer leg reports these)
        #: ``append_merges`` counts same-table append/append races the
        #: manifest-diff rebase absorbed (two writers extending one table)
        self.txn_stats = {"commits": 0, "merges": 0, "append_merges": 0,
                          "rebases": 0, "conflicts": 0,
                          "contract_rejections": 0}
        try:
            self.store.get_ref(_BRANCH_PREFIX + "main")
        except RefNotFound:
            root = Commit((), {}, "repository root", "system", self.clock())
            try:  # create-exclusive: a concurrent init's root is as good
                self.store.cas_ref(_BRANCH_PREFIX + "main", None,
                                   self.store.put(_pack(root.to_obj())))
            except RefConflict:
                pass

    def _bump_stat(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self.txn_stats[key] += n

    # -------------------------------------------------------------- plumbing
    def _load_commit(self, digest: str) -> Commit:
        return Commit.from_obj(_unpack(self.store.get(digest)))

    def _store_commit(self, commit: Commit) -> str:
        return self.store.put(_pack(commit.to_obj()))

    def head(self, branch: str) -> str:
        return self.store.get_ref(_BRANCH_PREFIX + branch)

    def branches(self) -> List[str]:
        return [r[len(_BRANCH_PREFIX):] for r in self.store.iter_refs()
                if r.startswith(_BRANCH_PREFIX)]

    def tags(self) -> List[str]:
        return [r[len(_TAG_PREFIX):] for r in self.store.iter_refs()
                if r.startswith(_TAG_PREFIX)]

    # --------------------------------------------------------------- resolve
    def resolve(self, ref: str) -> str:
        """Resolve branch / tag / commit digest / time-travel spec.

        Time travel (paper §5 "travels back in time"):
          ``main@1718000000``  — last commit on main at/before unix ts
          ``main~3``           — 3 first-parent steps back from main head
        """
        if "@" in ref:
            base, ts = ref.split("@", 1)
            return self._at_time(self.resolve(base), float(ts))
        if "~" in ref:
            base, n = ref.split("~", 1)
            digest = self.resolve(base)
            for _ in range(int(n)):
                parents = self._load_commit(digest).parents
                if not parents:
                    raise RefNotFound(f"{ref}: ran out of history")
                digest = parents[0]
            return digest
        if ref.startswith((_BRANCH_PREFIX, _TAG_PREFIX)):
            # fully-qualified spelling (``tag=v1.0`` / ``branch=main``) —
            # the exact names sync reports and ref listings print, so they
            # round-trip straight back into resolve
            return self.store.get_ref(ref)
        try:
            return self.head(ref)
        except RefNotFound:
            pass
        try:
            return self.store.get_ref(_TAG_PREFIX + ref)
        except RefNotFound:
            pass
        if "/" in ref:  # remote-tracking: ``origin/main`` (git spelling)
            rname, _, leaf = ref.partition("/")
            for tracking in (remote_tracking_ref(rname, leaf),
                             remote_tracking_tag_ref(rname, leaf)):
                try:
                    return self.store.get_ref(tracking)
                except RefNotFound:
                    pass
        if self.store.has(ref):
            return ref
        # commit digest prefix
        matches = [d for d in self.store.iter_objects() if d.startswith(ref)]
        if len(matches) == 1:
            return matches[0]
        raise RefNotFound(ref)

    def _at_time(self, digest: str, ts: float) -> str:
        cur: Optional[str] = digest
        while cur is not None:
            c = self._load_commit(cur)
            if c.ts <= ts:
                return cur
            cur = c.parents[0] if c.parents else None
        raise RefNotFound(f"no commit at/before ts={ts}")

    # ---------------------------------------------------------------- policy
    @staticmethod
    def branch_owner(branch: str) -> Optional[str]:
        return branch.split(".", 1)[0] if "." in branch else None

    def _check_write(self, branch: str, author: str, *, wap_token: bool):
        if branch == "main":
            if self.protect_main and not wap_token:
                raise PermissionDenied(
                    "main is write-audit-publish protected; use wap.publish()")
            return
        owner = self.branch_owner(branch)
        if owner is not None and owner != author:
            raise PermissionDenied(
                f"{author!r} cannot write to {branch!r} (owner {owner!r})")

    # ---------------------------------------------------------------- writes
    def create_branch(self, name: str, from_ref: str = "main", *,
                      author: str = "system") -> str:
        """Copy-on-write branch: one ref write, zero data copies (§5.4).

        Creation is create-exclusive: the ref is CAS'd *from absent*, so
        two concurrent creates of the same name have exactly one winner —
        the loser raises :class:`ReproError` and can never overwrite the
        winner's ref (the old check-then-set did exactly that)."""
        if name != "main" and self.branch_owner(name) not in (None, author):
            raise PermissionDenied(f"{author!r} cannot create {name!r}")
        if name in self.branches():
            raise ReproError(f"branch {name!r} exists")
        digest = self.resolve(from_ref)
        try:
            self.store.cas_ref(_BRANCH_PREFIX + name, None, digest)
        except RefConflict:
            raise ReproError(
                f"branch {name!r} exists (lost a concurrent create race)"
            ) from None
        return digest

    def delete_branch(self, name: str) -> None:
        if name == "main":
            raise PermissionDenied("cannot delete main")
        self.store.delete_ref(_BRANCH_PREFIX + name)

    def create_tag(self, name: str, ref: str) -> str:
        digest = self.resolve(ref)
        self.store.set_ref(_TAG_PREFIX + name, digest)
        return digest

    def delete_tag(self, name: str) -> None:
        self.store.delete_ref(_TAG_PREFIX + name)

    @tracing.span("catalog.commit")
    def commit(
        self,
        branch: str,
        table_updates: Mapping[str, Optional[str]],
        message: str,
        *,
        author: str = "system",
        meta: Optional[Dict[str, Any]] = None,
        read_tables: Optional[Sequence[str]] = None,
        base: Optional[str] = None,
        expected_head: Optional[str] = None,
        max_attempts: Optional[int] = None,
        _wap_token: bool = False,
        _contracts_update: bool = False,
    ) -> str:
        """Multi-table transaction: atomically update snapshot pointers on a
        branch.  ``None`` as snapshot digest deletes the table.

        The commit is an **optimistic table-level transaction**: its
        declared set is ``table_updates`` keys ∪ ``read_tables``, checked
        against ``base`` (the head the caller computed its writes from;
        defaults to the head read here).  On a ref-level CAS miss the
        commit *rebases* — re-reads the moved head, verifies no declared
        table changed snapshot since ``base``, rebuilds on the new head,
        retries (up to ``max_attempts``) — so concurrent commits to
        disjoint tables all land without any caller-visible conflict.
        Genuine overlap raises :class:`~.errors.TransactionConflict`.

        ``expected_head=`` pins the commit: exactly one CAS attempt
        against that digest, any movement raises ``TransactionConflict``
        with ``pinned=True`` — WAP publish uses this to guarantee the
        branch it stamps is byte-identical to the branch it audited.

        Data contracts on any written table are enforced here, before the
        ref moves, regardless of which path produced the commit."""
        self._check_write(branch, author, wap_token=_wap_token)
        if CONTRACTS_TABLE in table_updates and not _contracts_update:
            raise PermissionDenied(
                f"{CONTRACTS_TABLE!r} is reserved; use "
                "Catalog.add_contract()/drop_contract()")
        declared = set(table_updates) | set(read_tables or ())
        attempts_cap = (1 if expected_head is not None
                        else (max_attempts or DEFAULT_MAX_ATTEMPTS))
        head = expected_head if expected_head is not None else self.head(branch)
        if base is None:
            base = head
        base_tables = self._load_commit(base).tables
        attempts = 0
        while True:
            attempts += 1
            head_commit = self._load_commit(head)
            updates = dict(table_updates)
            if head != base:
                overlap = changed_tables(base_tables, head_commit.tables,
                                         declared)
                if overlap and expected_head is None:
                    # Manifest-diff escape hatch: when every overlapping
                    # table is an append/append race (both sides extended
                    # the base snapshot's manifest list verbatim), the
                    # file sets are disjoint by construction and the
                    # appends merge — same-table concurrent ingest lands
                    # with no caller-visible conflict.  Anything else
                    # (overwrite, compact, delete, declared read) stays a
                    # TransactionConflict.  Never under expected_head=:
                    # WAP publish pins byte-identical state.
                    merged = self._merge_table_appends(
                        overlap, updates, base_tables, head_commit.tables)
                    if merged is not None:
                        updates.update(merged)
                        overlap = []
                if overlap:
                    self._bump_stat("conflicts")
                    raise TransactionConflict(branch, overlap,
                                              attempts=attempts, base=base,
                                              pinned=expected_head is not None)
            tables = dict(head_commit.tables)
            for name, snap in updates.items():
                if snap is None:
                    tables.pop(name, None)
                else:
                    tables[name] = snap
            self._enforce_contracts(branch, head_commit.tables, tables)
            digest = self._store_commit(
                Commit((head,), tables, message, author, self.clock(),
                       meta or {}))
            try:
                self.store.cas_ref(_BRANCH_PREFIX + branch, head, digest)
            except RefConflict:
                if expected_head is not None:
                    self._bump_stat("conflicts")
                    raise TransactionConflict(
                        branch, [], attempts=attempts, base=base,
                        pinned=True) from None
                if attempts >= attempts_cap:
                    self._bump_stat("conflicts")
                    raise TransactionConflict(
                        branch, [], attempts=attempts, base=base,
                        exhausted=True) from None
                self._bump_stat("rebases")
                head = self.head(branch)
                continue
            self._bump_stat("commits")
            return digest

    def _merge_table_appends(
        self,
        overlap: Sequence[str],
        updates: Mapping[str, Optional[str]],
        base_tables: Mapping[str, str],
        head_tables: Mapping[str, str],
    ) -> Optional[Dict[str, str]]:
        """Try to absorb an overlapping head movement as append merges.

        Returns ``{table: merged snapshot digest}`` when EVERY overlapping
        table is a same-table append/append race resolvable by
        :func:`~.txn.rebase_append`; None if any single one is not — the
        merge is all-or-nothing so a commit never lands half its declared
        set rebased one way and half another."""
        from .txn import rebase_append

        io = self._table_io()
        merged: Dict[str, str] = {}
        for table in overlap:
            ours = updates.get(table)
            if table not in updates or ours is None:
                return None  # declared read or delete: genuine conflict
            rebased = rebase_append(io, base_tables.get(table),
                                    head_tables.get(table), ours)
            if rebased is None:
                return None
            merged[table] = rebased
        self._bump_stat("append_merges", len(merged))
        return merged

    # ----------------------------------------------------------------- reads
    def tables(self, ref: str) -> Dict[str, str]:
        return dict(self._load_commit(self.resolve(ref)).tables)

    def input_digests(self, ref: str,
                      names: Optional[Sequence[str]] = None) -> Dict[str, str]:
        """Snapshot digests of (a subset of) tables at ``ref`` — the data half
        of a node's run-cache key: a pipeline reading these tables is
        re-executed iff one of these digests (or its code) changes."""
        tables = self.tables(ref)
        if names is None:
            return tables
        return {n: tables[n] for n in names if n in tables}

    def snapshot_of(self, ref: str, table: str) -> str:
        tables = self.tables(ref)
        if table not in tables:
            from .errors import TableNotFound
            raise TableNotFound(f"{table!r} not in {ref!r}")
        return tables[table]

    def log(self, ref: str, *, first_parent: bool = True) -> List[str]:
        out, cur = [], self.resolve(ref)
        seen: Set[str] = set()
        stack = [cur]
        while stack:
            digest = stack.pop(0)
            if digest in seen:
                continue
            seen.add(digest)
            out.append(digest)
            parents = self._load_commit(digest).parents
            if first_parent:
                stack.extend(parents[:1])
            else:
                stack.extend(parents)
        return out

    def commit_info(self, ref: str) -> Commit:
        return self._load_commit(self.resolve(ref))

    # ----------------------------------------------------------------- merge
    def _ancestors(self, digest: str) -> Set[str]:
        seen: Set[str] = set()
        stack = [digest]
        while stack:
            d = stack.pop()
            if d in seen:
                continue
            seen.add(d)
            stack.extend(self._load_commit(d).parents)
        return seen

    def merge_base(self, a: str, b: str) -> Optional[str]:
        """Lowest common ancestor (first found walking a's history by ts)."""
        anc_b = self._ancestors(b)
        best, best_ts = None, -1.0
        for d in self._ancestors(a):
            if d in anc_b:
                ts = self._load_commit(d).ts
                if ts > best_ts:
                    best, best_ts = d, ts
        return best

    def merge(self, src_ref: str, dst_branch: str, *, author: str = "system",
              message: Optional[str] = None, _wap_token: bool = False,
              max_attempts: Optional[int] = None) -> str:
        """Fast-forward when possible, else 3-way at table granularity.

        Conflict rule (Nessie semantics): a table changed on *both* sides
        since the merge base conflicts unless both sides reached the same
        snapshot.

        The merge is itself an optimistic transaction: a ref-level CAS
        miss (the destination moved while we computed the merge) triggers
        a full recompute against the new head and a retry — the 3-way
        table comparison re-run per attempt *is* the semantic conflict
        check, so a concurrent commit to tables the source didn't touch
        never aborts the merge.  A fast-forward whose destination moves
        degrades gracefully into a 3-way merge on retry.  Contracts on
        every table the merge changes are enforced before the ref moves —
        on the fast-forward path too (a branch can fast-forward past a
        contract added after it forked)."""
        self._check_write(dst_branch, author, wap_token=_wap_token)
        src = self.resolve(src_ref)
        attempts_cap = max_attempts or DEFAULT_MAX_ATTEMPTS
        attempts = 0
        dst = self.head(dst_branch)
        while True:
            attempts += 1
            if src == dst or src in self._ancestors(dst):
                # already merged (a retry can observe its own landed work
                # or a concurrent identical merge) — idempotent success
                return dst
            dst_tables = self._load_commit(dst).tables
            if dst in self._ancestors(src):  # fast-forward
                src_tables = self._load_commit(src).tables
                self._enforce_contracts(dst_branch, dst_tables, src_tables)
                if try_cas_ref(self.store, _BRANCH_PREFIX + dst_branch,
                               dst, src):
                    self._bump_stat("merges")
                    return src
                dst = self._rebase_or_exhaust(dst_branch, attempts,
                                              attempts_cap)
                continue
            base = self.merge_base(src, dst)
            base_tables = self._load_commit(base).tables if base else {}
            src_tables = self._load_commit(src).tables
            conflicts, merged = [], dict(dst_tables)
            for name in sorted(set(base_tables) | set(src_tables)
                               | set(dst_tables)):
                b = base_tables.get(name)
                s = src_tables.get(name)
                d = dst_tables.get(name)
                if s == d:
                    continue
                src_changed, dst_changed = (s != b), (d != b)
                if src_changed and dst_changed:
                    conflicts.append(name)
                elif src_changed:
                    if s is None:
                        merged.pop(name, None)
                    else:
                        merged[name] = s
            if conflicts:
                self._bump_stat("conflicts")
                raise MergeConflict(conflicts)
            self._enforce_contracts(dst_branch, dst_tables, merged)
            commit = Commit(
                (dst, src), merged,
                message or f"merge {src_ref} into {dst_branch}",
                author, self.clock(), {"merge_base": base},
            )
            digest = self._store_commit(commit)
            if try_cas_ref(self.store, _BRANCH_PREFIX + dst_branch,
                           dst, digest):
                self._bump_stat("merges")
                return digest
            dst = self._rebase_or_exhaust(dst_branch, attempts, attempts_cap)

    def _rebase_or_exhaust(self, dst_branch: str, attempts: int,
                           attempts_cap: int) -> str:
        """CAS miss bookkeeping for merge: either hand back the moved head
        for another attempt or give up loudly."""
        if attempts >= attempts_cap:
            self._bump_stat("conflicts")
            raise TransactionConflict(dst_branch, [], attempts=attempts,
                                      exhausted=True)
        self._bump_stat("rebases")
        return self.head(dst_branch)

    def diff(self, ref_a: str, ref_b: str) -> Dict[str, tuple]:
        """Tables whose snapshot differs between two refs."""
        ta, tb = self.tables(ref_a), self.tables(ref_b)
        out = {}
        for name in sorted(set(ta) | set(tb)):
            if ta.get(name) != tb.get(name):
                out[name] = (ta.get(name), tb.get(name))
        return out

    # ---------------------------------------------------------- transactions
    def transaction(self, branch: str, *, author: str = "system",
                    io=None) -> Transaction:
        """Open an optimistic read/write transaction against ``branch``
        (see :class:`~.txn.Transaction`)."""
        return Transaction(self, branch, author=author, io=io)

    # ------------------------------------------------------------- contracts
    def _table_io(self):
        if self._io is None:
            from .table import TableIO
            self._io = TableIO(self.store)
        return self._io

    def _load_contract_specs(self, contracts_digest: Optional[str]
                             ) -> Dict[str, Contract]:
        if contracts_digest is None:
            return {}
        cached = self._contracts_cache.get(contracts_digest)
        if cached is None:
            cached = unpack_contracts(self.store.get(contracts_digest))
            self._contracts_cache[contracts_digest] = cached
        return cached

    def contracts(self, ref: str = "main") -> Dict[str, Contract]:
        """Contracts in force at ``ref`` (table name → contract)."""
        tables = self.tables(ref)
        return dict(self._load_contract_specs(tables.get(CONTRACTS_TABLE)))

    def _enforce_contracts(self, branch: str,
                           old_tables: Mapping[str, str],
                           new_tables: Mapping[str, str]) -> None:
        """Gate a prospective commit's tables against the contracts *it*
        carries.  Checked for every table whose snapshot OR contract
        changed relative to the current head — so attaching a contract
        over already-bad data is rejected at attach time, and unchanged
        tables never cost a data read.  Evaluation is memoized by
        (contracts blob, table, snapshot): a rebase retry re-checks the
        same snapshots for free."""
        new_cdig = new_tables.get(CONTRACTS_TABLE)
        if new_cdig is None:
            return
        new_specs = self._load_contract_specs(new_cdig)
        if not new_specs:
            return
        old_specs = self._load_contract_specs(old_tables.get(CONTRACTS_TABLE))
        for table, contract in new_specs.items():
            snap = new_tables.get(table)
            if snap is None:
                continue  # contracted table absent: nothing to validate
            if (snap == old_tables.get(table)
                    and contract == old_specs.get(table)):
                continue  # neither data nor contract moved past the head
            key = (new_cdig, table, snap)
            failures = self._contract_results.get(key)
            if failures is None:
                frame = self._table_io().read(snap)
                failures = evaluate(contract, frame)
                self._contract_results[key] = failures
            if failures:
                self._bump_stat("contract_rejections")
                raise ContractViolation(branch, table, failures)

    def add_contract(self, table: str, rules: Sequence[Rule], *,
                     branch: str = "main", author: str = "system",
                     _wap_token: bool = False) -> str:
        """Attach (or replace) the contract on ``table`` at ``branch``.

        The attach is itself a contract-gated commit: if the table's
        current snapshot violates the new rules, the attach is rejected —
        a contract can never be in force over data that fails it."""
        if table == CONTRACTS_TABLE:
            raise PermissionDenied(f"cannot contract {CONTRACTS_TABLE!r}")
        specs = dict(self.contracts(branch))
        specs[table] = Contract(table, tuple(rules), author)
        digest = self.store.put(pack_contracts(specs))
        return self.commit(
            branch, {CONTRACTS_TABLE: digest},
            f"contract: {table} ({len(rules)} rule(s))", author=author,
            _wap_token=_wap_token, _contracts_update=True)

    def drop_contract(self, table: str, *, branch: str = "main",
                      author: str = "system",
                      _wap_token: bool = False) -> str:
        specs = dict(self.contracts(branch))
        if table not in specs:
            raise ReproError(f"no contract on {table!r} at {branch!r}")
        del specs[table]
        digest = self.store.put(pack_contracts(specs))
        return self.commit(
            branch, {CONTRACTS_TABLE: digest},
            f"contract: drop {table}", author=author,
            _wap_token=_wap_token, _contracts_update=True)
