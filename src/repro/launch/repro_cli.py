"""``repro`` CLI — the paper's bauplan-style command surface (§4–5):

  repro branch <user.branch> [--from REF]      create a CoW branch
  repro checkout <ref>                         resolve + print a ref
  repro run --pipeline data --branch B         run a pipeline, get a run_id
  repro run --id RUN_ID --branch B             REPLAY a past run (Listing 3)
  repro run ... --executor process             local process pool (GIL-bound
                                               nodes; bit-identical commits)
  repro run ... --executor remote              lease nodes to `repro worker`
                                               processes sharing the store
  repro worker [--once|--max-idle SEC]         pull-based worker loop
  repro status <run-id>                        live per-node lease/heartbeat/
                                               cache state (docs/executor.md)
  repro query "SELECT COUNT(*) FROM t" --ref R tiny read-path query
  repro log <ref> / branches / runs            inspect the catalog
  repro contract add T not_empty no_nans       attach catalog-enforced data
                                               contracts to a table (see
                                               docs/catalog.md)
  repro contract list [--ref R] / drop T       inspect / detach contracts

Multi-host (git-remote semantics over the object store — see
docs/remote_store.md):

  repro remote add origin URL                  name a remote (http://, s3://
                                               or a path)
  repro push --branch B [--remote origin]      publish closure + cache + runs
  repro push main 'exp/*' --tags 'v*'          atomic multi-ref push (globs;
                                               all refs land or none do)
  repro pull --branch B [--remote origin]      fetch + fast-forward
  repro clone URL DEST [--branch B]            new lake from a remote (+tags)
  repro serve --root DIR --port P              loopback object-store server
  repro serve --root DIR --s3 [--bucket B]     stub S3 server (same tree,
                                               S3 REST dialect)

Model serving on immutable refs (docs/serving.md — deployment is a
catalog tag flip, rollback is time-travel):

  repro serve --replicas 2 --watch-tag serving/prod --smoke
                                               replica fleet; each replica
                                               pins an engine to the tag's
                                               checkpoint commit
  repro rollout --to <ckpt-ref>                CAS-flip serving/prod
  repro rollout --to <ckpt-ref> --canary 8     ...gated: flip only if WAP
                                               expectations over live canary
                                               metrics pass
  repro rollback                               flip back to serving/prev
  repro gc [--dry-run] [--drop-cache]          mark-and-sweep the local lake
  repro gc --remote origin                     remote-side GC: server-side
                                               mark from the REMOTE's refs,
                                               sweep there
  repro gc --prune-age 3600                    upload-age grace window —
                                               with the GC generation token
                                               this makes gc safe to run
                                               concurrently with pushes

Transfers are concurrent (--jobs N workers; --jobs 1 = sequential) and
move large blobs as compressed wire frames (paid for once, at write time).

"CLI is all you need": no catalog service to provision, no client API to
learn — the same ergonomics claim the paper demonstrates, over the tensor
lake.  Example session in examples/quickstart.py.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

import numpy as np

from repro.core import Lake, ObjectStore, SyncError, connect, serve_http
from repro.core import sync as sync_mod
from repro.data import build_data_pipeline


def _pipeline(name: str, seq_len: int):
    if name == "data":
        return build_data_pipeline(seq_len)
    raise SystemExit(f"unknown pipeline {name!r} (built-in: data)")


_QUERY_RE = re.compile(
    r"^\s*select\s+(count\(\*\)|[\w,\s*]+)\s+from\s+(\w+)\s*"
    r"(?:where\s+(\w+)\s*(=|>|<|>=|<=)\s*([-\d.]+))?\s*$", re.I)


def _query(lake: Lake, sql: str, ref: str):
    """Minimal SELECT over one table — the paper's Listing 3 read path.
    (Full engines read the same snapshots via the Iceberg-like manifests.)"""
    m = _QUERY_RE.match(sql)
    if not m:
        raise SystemExit(
            "supported: SELECT count(*)|cols FROM table [WHERE col OP num]")
    proj, table, wcol, wop, wval = m.groups()
    cols = lake.read_table(ref, table)
    if wcol:
        import operator
        ops = {"=": operator.eq, ">": operator.gt, "<": operator.lt,
               ">=": operator.ge, "<=": operator.le}
        mask = ops[wop](cols[wcol], float(wval))
        cols = {k: v[mask] for k, v in cols.items()}
    n = next(iter(cols.values())).shape[0] if cols else 0
    if proj.strip().lower() == "count(*)":
        print(n)
        return
    names = [c.strip() for c in proj.split(",") if c.strip() != "*"] \
        or list(cols)
    for i in range(min(n, 20)):
        print({k: np.asarray(cols[k][i]).tolist() for k in names})
    if n > 20:
        print(f"... ({n} rows)")


def _remotes_dir(lake: Lake) -> Path:
    return Path(lake.store.root) / "remotes"


def _resolve_remote(lake: Lake, spec: str, *, allow_delete: bool = False):
    """A remote spec is a configured name (``repro remote add``) or a
    URL/path used directly.  A bare name that is neither configured nor an
    existing directory is an error — silently creating an empty store named
    after a typo'd remote would make a push look published when nothing
    left the machine.  ``allow_delete`` opens the remote-side GC sweep path
    (only ``repro gc --remote`` passes it)."""
    if "://" in spec:
        return connect(spec, allow_delete=allow_delete)
    if "/" not in spec and "\\" not in spec:
        cfg = _remotes_dir(lake) / spec
        if cfg.exists():
            return connect(cfg.read_text().strip(),
                           allow_delete=allow_delete)
        if not Path(spec).is_dir():
            raise SystemExit(
                f"unknown remote {spec!r}: configure it with "
                f"`repro remote add {spec} URL` or pass a URL/path")
    return connect(spec, allow_delete=allow_delete)


def _add_sync_args(p):
    p.add_argument("refspecs", nargs="*", metavar="BRANCH",
                   help="branch names or globs; several move as ONE atomic "
                        "multi-ref operation (all refs update or none do)")
    p.add_argument("--branch", default=None,
                   help="single branch (kept for scripts; same as one "
                        "positional BRANCH)")
    p.add_argument("--tags", action="append", default=None, metavar="PATTERN",
                   help="also sync tags matching PATTERN (glob; repeatable)")
    p.add_argument("--remote", action="append", default=None,
                   help="configured remote name, or a URL/path (default: "
                        "origin; repeatable on push — one closure walk "
                        "fans out to every destination)")
    p.add_argument("--force", action="store_true",
                   help="allow a non-fast-forward ref update / tag clobber")
    p.add_argument("--jobs", type=int, default=None, metavar="N",
                   help="concurrent transfer workers (default: 8; 1 = "
                        "sequential)")
    p.add_argument("--no-cache-entries", action="store_true",
                   help="skip run-cache entry transfer (see the trust "
                        "model in docs/remote_store.md)")
    p.add_argument("--no-runs", action="store_true",
                   help="skip run-ledger manifest transfer")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="repro")
    ap.add_argument("--lake", default=".lake")
    sub = ap.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("branch")
    b.add_argument("name")
    b.add_argument("--from", dest="from_ref", default="main")
    b.add_argument("--author", default=None)

    c = sub.add_parser("checkout")
    c.add_argument("ref")

    r = sub.add_parser("run")
    r.add_argument("--pipeline", default="data")
    r.add_argument("--seq-len", type=int, default=256)
    r.add_argument("--branch", required=True)
    r.add_argument("--id", dest="run_id", default=None,
                   help="replay this run id instead of a fresh run")
    r.add_argument("--author", default="cli")
    r.add_argument("--no-cache", action="store_true",
                   help="ignore the run cache: re-execute every node")
    r.add_argument("--jobs", type=int, default=None, metavar="N",
                   help="max concurrent DAG nodes (default: auto)")
    r.add_argument("--executor", choices=["thread", "process", "remote"],
                   default="thread",
                   help="worker backend: thread (default), process (local "
                        "process pool for GIL-bound nodes), remote (lease "
                        "nodes to `repro worker` processes sharing the "
                        "store)")
    r.add_argument("--lease-ttl", type=float, default=30.0, metavar="SEC",
                   help="worker heartbeat deadline; an expired lease means "
                        "the worker is presumed dead and the node is "
                        "re-leased (default: 30)")
    r.add_argument("--max-attempts", type=int, default=3, metavar="N",
                   help="poison pill: fail the run after N lease claims of "
                        "one node (default: 3)")
    r.add_argument("--wait-timeout", type=float, default=None,
                   metavar="SEC",
                   help="--executor remote: fail if no node makes progress "
                        "for this long (default: wait forever)")

    st = sub.add_parser("status",
                        help="live per-node lease/heartbeat/cache state of "
                             "a run (exec id or ledger run id, prefixes ok)")
    st.add_argument("run_id")

    w = sub.add_parser("worker",
                       help="execute leased nodes for runs started with "
                            "--executor remote (shares the lake store; "
                            "code is matched by pipeline hash, never "
                            "shipped)")
    w.add_argument("--pipeline", default="data",
                   help="pipeline(s) this worker can execute (comma-"
                        "separated; built-in: data)")
    w.add_argument("--seq-len", type=int, default=256)
    w.add_argument("--name", default=None,
                   help="lease owner name (default: worker-<pid>)")
    w.add_argument("--ttl", type=float, default=10.0,
                   help="heartbeat lease ttl in seconds (default: 10)")
    w.add_argument("--poll", type=float, default=0.05,
                   help="idle poll interval in seconds (default: 0.05)")
    w.add_argument("--once", action="store_true",
                   help="claim and execute at most one node, then exit")
    w.add_argument("--max-idle", type=float, default=None, metavar="SEC",
                   help="exit after this long with no claimable work "
                        "(default: poll forever)")

    cc = sub.add_parser("cache", help="inspect / clear the run cache")
    cc.add_argument("action", choices=["stats", "clear"])

    g = sub.add_parser("gc", help="mark-and-sweep unreachable objects")
    g.add_argument("--dry-run", action="store_true",
                   help="report what would be swept without deleting")
    g.add_argument("--drop-cache", action="store_true",
                   help="drop run-cache entries first and sweep what only "
                        "the cache kept alive")
    g.add_argument("--remote", default=None, metavar="NAME",
                   help="collect the named remote instead of the local "
                        "lake: mark from the REMOTE's own refs, sweep via "
                        "its delete_object — local state is never trusted")
    g.add_argument("--prune-age", type=float, default=None,
                   metavar="SECONDS",
                   help="upload-age grace window: never sweep an object "
                        "younger than this (default: 3600 — the safety "
                        "margin that lets gc run concurrently with "
                        "pushes; 0 sweeps everything unreachable)")

    cp = sub.add_parser(
        "compact",
        help="rewrite a table's small tensorfile fragments into "
             "target-sized files as a new snapshot with digest-provably "
             "identical logical contents (the maintenance half of "
             "streaming ingestion)")
    cp.add_argument("table")
    cp.add_argument("--branch", default="main")
    cp.add_argument("--author", default="compactor")
    cp.add_argument("--target-rows", type=int, default=None, metavar="N",
                    help="rows per output file (default: the lake's "
                         "target_rows_per_file)")
    cp.add_argument("--no-history", action="store_true",
                    help="start a fresh snapshot chain instead of keeping "
                         "the compacted snapshot as parent — the old "
                         "fragments become GC-collectable once the grace "
                         "window passes")
    cp.add_argument("--max-attempts", type=int, default=4,
                    help="retries when concurrent ingestion keeps moving "
                         "the table (ingestion always wins the race)")

    q = sub.add_parser("query")
    q.add_argument("sql")
    q.add_argument("--ref", default="main")

    lg = sub.add_parser("log")
    lg.add_argument("ref")

    sub.add_parser("branches")
    sub.add_parser("runs")

    ct = sub.add_parser("contract",
                        help="catalog-enforced data contracts: rules the "
                             "ref update itself checks on every commit/"
                             "merge/publish touching the table")
    ct_sub = ct.add_subparsers(dest="contract_cmd", required=True)
    ct_add = ct_sub.add_parser(
        "add", help="attach rules to a table (current data is validated "
                    "first: a contract is never in force over data that "
                    "fails it)")
    ct_add.add_argument("table")
    ct_add.add_argument("rules", nargs="+",
                        help="rule specs: not_empty | no_nans[:cols] | "
                             "column_range:col,lo,hi | "
                             "columns_required:cols")
    ct_add.add_argument("--branch", default="main")
    ct_add.add_argument("--author", default="cli")
    ct_list = ct_sub.add_parser("list")
    ct_list.add_argument("--ref", default="main")
    ct_drop = ct_sub.add_parser("drop")
    ct_drop.add_argument("table")
    ct_drop.add_argument("--branch", default="main")
    ct_drop.add_argument("--author", default="cli")

    rm = sub.add_parser("remote", help="manage named remotes")
    rm_sub = rm.add_subparsers(dest="remote_cmd", required=True)
    rm_add = rm_sub.add_parser("add")
    rm_add.add_argument("name")
    rm_add.add_argument("url", help="http(s)://host:port or a store path")
    rm_sub.add_parser("list")

    _add_sync_args(sub.add_parser(
        "push", help="publish a branch closure to a remote"))
    _add_sync_args(sub.add_parser(
        "pull", help="fetch a branch closure from a remote"))

    cl = sub.add_parser("clone", help="materialize a lake from a remote")
    cl.add_argument("url")
    cl.add_argument("dest")
    cl.add_argument("--branch", default=None,
                    help="single branch (default: every remote branch)")
    cl.add_argument("--no-tags", action="store_true",
                    help="skip pulling remote tags")
    cl.add_argument("--jobs", type=int, default=None, metavar="N",
                    help="concurrent transfer workers")

    sv = sub.add_parser("serve", help="serve a store over loopback HTTP")
    sv.add_argument("--root", default=None,
                    help="store directory (default: the --lake store)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8750)
    sv.add_argument("--s3", action="store_true",
                    help="serve the S3-compatible REST dialect instead of "
                         "the msgpack protocol (clients connect with "
                         "s3://host:port/BUCKET)")
    sv.add_argument("--bucket", default="lake",
                    help="bucket name for --s3 (default: lake)")
    sv.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="model-fleet mode: serve N tag-watching replica "
                         "engines instead of the object store")
    sv.add_argument("--watch-tag", default="serving/prod",
                    help="catalog tag the fleet deploys from "
                         "(default: serving/prod)")
    sv.add_argument("--arch", default="paper-demo")
    sv.add_argument("--smoke", action="store_true",
                    help="smoke-sized model config")
    sv.add_argument("--slots", type=int, default=4,
                    help="decode slots per replica (continuous batching)")
    sv.add_argument("--max-len", type=int, default=128)
    sv.add_argument("--mode", choices=["continuous", "fixed"],
                    default="continuous")
    sv.add_argument("--requests", type=int, default=16,
                    help="synthetic requests to serve before exiting")
    sv.add_argument("--gen-tokens", type=int, default=8)
    sv.add_argument("--poll-every", type=int, default=4,
                    help="fleet steps between tag polls")

    ro = sub.add_parser(
        "rollout", help="deploy a checkpoint: CAS-flip the serving tag "
                        "(optionally canary-gated by WAP expectations)")
    ro.add_argument("--to", dest="to_ref", required=True,
                    help="checkpoint ref to deploy (branch/tag/commit; a "
                         "branch resolves to its latest checkpoint)")
    ro.add_argument("--tag", default="serving/prod")
    ro.add_argument("--canary", type=int, default=None, metavar="N",
                    help="serve N live requests from a canary replica "
                         "pinned to the candidate and flip only if the "
                         "WAP audit over its metric table passes")
    ro.add_argument("--arch", default="paper-demo")
    ro.add_argument("--smoke", action="store_true")
    ro.add_argument("--max-len", type=int, default=128)
    ro.add_argument("--slots", type=int, default=4)
    ro.add_argument("--gen-tokens", type=int, default=8)

    rb = sub.add_parser(
        "rollback", help="flip the serving tag back to serving/prev")
    rb.add_argument("--tag", default="serving/prod")

    args = ap.parse_args(argv)

    if args.cmd == "clone":  # no existing lake needed
        remote = connect(args.url)
        _local, reports = sync_mod.clone(
            remote, args.dest, branch=args.branch,
            tags=() if args.no_tags else ("*",), jobs=args.jobs)
        dest_remotes = Path(args.dest) / "remotes"
        dest_remotes.mkdir(parents=True, exist_ok=True)
        (dest_remotes / "origin").write_text(args.url)
        for rep in reports:
            print(rep.summary())
        return
    if args.cmd == "serve" and args.replicas:
        from repro.configs import full_config, smoke_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import run_fleet

        enable_compile_cache()
        cfg = (smoke_config(args.arch) if args.smoke
               else full_config(args.arch))
        fleet = run_fleet(Lake(args.lake), cfg, replicas=args.replicas,
                          slots=args.slots, max_len=args.max_len,
                          watch_tag=args.watch_tag,
                          poll_every=args.poll_every, mode=args.mode,
                          requests=args.requests,
                          gen_tokens=args.gen_tokens)
        print(json.dumps({
            "replicas": args.replicas, "watch_tag": args.watch_tag,
            "target": fleet.target[:12], "served": len(fleet.completed),
            "steps": fleet.steps, "rollouts": fleet.rollouts}))
        return
    if args.cmd == "serve":
        import time as _time

        root = args.root or args.lake
        if args.s3:
            from repro.core.s3stub import serve_s3

            httpd, url = serve_s3(root, host=args.host, port=args.port,
                                  bucket=args.bucket)
        else:
            httpd, url = serve_http(ObjectStore(root), host=args.host,
                                    port=args.port)
        print(f"serving {root} at {url}", flush=True)
        try:  # the serve_http daemon thread accepts requests; just block
            while True:
                _time.sleep(3600)
        except KeyboardInterrupt:
            httpd.shutdown()
        return

    lake = Lake(args.lake)

    if args.cmd == "branch":
        author = args.author or args.name.split(".")[0]
        digest = lake.catalog.create_branch(args.name, args.from_ref,
                                            author=author)
        print(f"{args.name} -> {digest[:12]} (copy-on-write)")
    elif args.cmd == "checkout":
        print(lake.catalog.resolve(args.ref))
    elif args.cmd == "run":
        from repro.core.errors import ContractViolation, TransactionConflict

        pipe = _pipeline(args.pipeline, args.seq_len)
        exec_kw = dict(executor=args.executor, lease_ttl=args.lease_ttl,
                       max_attempts=args.max_attempts,
                       wait_timeout=args.wait_timeout)
        try:
            if args.run_id:
                rep = lake.replay(args.run_id, pipe, branch=args.branch,
                                  author=args.author,
                                  use_cache=not args.no_cache,
                                  jobs=args.jobs, **exec_kw)
                print(json.dumps({"replayed": args.run_id,
                                  "replay_run_id": rep.replay_run_id,
                                  "branch": rep.branch,
                                  "bit_exact": rep.bit_exact}))
            else:
                res = lake.run(pipe, branch=args.branch, author=args.author,
                               use_cache=not args.no_cache, jobs=args.jobs,
                               **exec_kw)
                out = {"run_id": res.run_id,
                       "commit": res.commit[:12],
                       "outputs": list(res.outputs),
                       "cache_hits": res.cache_hits,
                       "cache_misses": res.cache_misses}
                rebases = lake.catalog.txn_stats["rebases"]
                if rebases:  # concurrent writers absorbed transparently
                    out["txn_rebases"] = rebases
                print(json.dumps(out))
        except ContractViolation as e:
            raise SystemExit(
                f"commit rejected by data contract: {e}") from None
        except TransactionConflict as e:
            raise SystemExit(
                f"commit lost to concurrent writers on the same tables: "
                f"{e} (rerun to retry from the new head)") from None
    elif args.cmd == "status":
        from repro.core.errors import ReproError

        try:
            print(json.dumps(lake.run_status(args.run_id), indent=2,
                             sort_keys=True, default=str))
        except ReproError as e:
            raise SystemExit(str(e)) from None
    elif args.cmd == "worker":
        import os as _os

        pipelines = [_pipeline(name.strip(), args.seq_len)
                     for name in args.pipeline.split(",") if name.strip()]
        svc = lake.worker(pipelines,
                          name=args.name or f"worker-{_os.getpid()}",
                          ttl=args.ttl, poll=args.poll)
        if args.once:
            did = svc.run_once()
            print(json.dumps({"worker": svc.name, "nodes_done": int(did)}))
        else:
            try:
                done = svc.serve_forever(max_idle=args.max_idle)
            except KeyboardInterrupt:
                done = svc.nodes_done
            print(json.dumps({"worker": svc.name, "nodes_done": done}))
    elif args.cmd == "cache":
        if args.action == "stats":
            print(json.dumps({"entries": len(lake.run_cache)}))
        else:
            print(json.dumps({"cleared": lake.run_cache.clear()}))
    elif args.cmd == "gc":
        from repro.core.gc import DEFAULT_PRUNE_AGE, collect

        if args.remote:
            # remote-side GC: every read and delete goes through the
            # remote itself — a stale local mirror can neither protect
            # nor doom a remote object
            store = _resolve_remote(lake, args.remote, allow_delete=True)
        else:
            store = lake.store
        prune_age = (DEFAULT_PRUNE_AGE if args.prune_age is None
                     else max(0.0, args.prune_age))
        rep = collect(store, dry_run=args.dry_run,
                      drop_cache=args.drop_cache, prune_age=prune_age)
        print(json.dumps({"target": args.remote or "local",
                          "live": rep.live, "swept": rep.swept,
                          "bytes_freed": rep.bytes_freed,
                          "skipped_young": rep.skipped_young,
                          "prune_age": prune_age,
                          "generation": rep.generation,
                          "mode": rep.mode,
                          "dry_run": args.dry_run}))
    elif args.cmd == "compact":
        from repro.core.compact import compact_table
        from repro.core.errors import ReproError

        # compaction is an operator/maintenance action like contract
        # administration: it may touch a WAP-protected main directly —
        # losslessness is enforced internally by the digest check
        try:
            rep = compact_table(
                lake.catalog, args.table, branch=args.branch,
                author=args.author,
                target_rows_per_file=args.target_rows,
                keep_history=not args.no_history,
                max_attempts=args.max_attempts, _wap_token=True)
        except ReproError as e:
            raise SystemExit(str(e)) from None
        print(json.dumps({"table": args.table, "branch": args.branch,
                          "files_before": rep.files_before,
                          "files_after": rep.files_after,
                          "rows": rep.rows,
                          "bytes_read": rep.bytes_read,
                          "bytes_written": rep.bytes_written,
                          "snapshot": rep.new_snapshot[:12],
                          "logical_digest": rep.logical_digest[:12]}))
    elif args.cmd == "query":
        _query(lake, args.sql, args.ref)
    elif args.cmd == "log":
        for d in lake.catalog.log(args.ref):
            info = lake.catalog.commit_info(d)
            print(f"{d[:12]} {info.author:12s} {info.message}")
    elif args.cmd == "branches":
        for name in sorted(lake.catalog.branches()):
            print(name)
    elif args.cmd == "contract":
        from repro.core import parse_rule_spec
        from repro.core.errors import ContractViolation, ReproError

        # contract administration is an operator action: it may touch a
        # WAP-protected main directly (the attach itself is still gated
        # by the new rules against the current data)
        try:
            if args.contract_cmd == "add":
                rules = [parse_rule_spec(s) for s in args.rules]
                digest = lake.catalog.add_contract(
                    args.table, rules, branch=args.branch,
                    author=args.author, _wap_token=True)
                print(json.dumps({"table": args.table,
                                  "branch": args.branch,
                                  "rules": [r.name for r in rules],
                                  "commit": digest[:12]}))
            elif args.contract_cmd == "drop":
                digest = lake.catalog.drop_contract(
                    args.table, branch=args.branch, author=args.author,
                    _wap_token=True)
                print(json.dumps({"dropped": args.table,
                                  "branch": args.branch,
                                  "commit": digest[:12]}))
            else:  # list
                specs = lake.catalog.contracts(args.ref)
                print(json.dumps(
                    {t: [r.name for r in c.rules]
                     for t, c in sorted(specs.items())}, indent=2))
        except ContractViolation as e:
            raise SystemExit(
                f"refused: {e} (fix the data or adjust the rules)"
            ) from None
        except ReproError as e:
            raise SystemExit(str(e)) from None
    elif args.cmd == "runs":
        for rid in lake.ledger.runs():
            print(rid)
    elif args.cmd == "remote":
        if args.remote_cmd == "add":
            if "/" in args.name or "\\" in args.name or \
                    args.name.startswith("."):
                raise SystemExit(f"bad remote name {args.name!r}")
            _remotes_dir(lake).mkdir(parents=True, exist_ok=True)
            (_remotes_dir(lake) / args.name).write_text(args.url)
            print(f"{args.name} -> {args.url}")
        else:
            d = _remotes_dir(lake)
            if d.is_dir():
                for cfg in sorted(d.iterdir()):
                    print(f"{cfg.name}\t{cfg.read_text().strip()}")
    elif args.cmd == "rollout":
        from repro.checkpoint import latest_checkpoint
        from repro.serving import canary_rollout, flip_tag

        target = latest_checkpoint(lake, args.to_ref) or args.to_ref
        if args.canary:
            from repro.configs import full_config, smoke_config
            from repro.launch.compile_cache import enable_compile_cache

            enable_compile_cache()
            cfg = (smoke_config(args.arch) if args.smoke
                   else full_config(args.arch))
            rng = np.random.default_rng(0)
            reqs = [(rid,
                     rng.integers(3, cfg.vocab_size,
                                  size=int(rng.integers(
                                      4, args.max_len - args.gen_tokens))
                                  ).astype(np.int32),
                     args.gen_tokens)
                    for rid in range(args.canary)]
            rep = canary_rollout(lake, cfg, target, reqs, tag=args.tag,
                                 slots=args.slots, max_len=args.max_len)
        else:
            rep = flip_tag(lake, target, tag=args.tag)
        print(json.dumps(rep.to_obj()))
        if not rep.flipped and rep.reason != "already current":
            raise SystemExit(1)
    elif args.cmd == "rollback":
        from repro.core.errors import RefNotFound
        from repro.serving import rollback as _rollback

        try:
            print(json.dumps(_rollback(lake, tag=args.tag).to_obj()))
        except RefNotFound as e:
            raise SystemExit(str(e)) from None
    elif args.cmd in ("push", "pull"):
        remote_specs = args.remote or ["origin"]
        if len(remote_specs) > 1 and args.cmd == "pull":
            raise SystemExit("pull: --remote may be given once (fan-out "
                             "is a push concept; pull merges ONE remote's "
                             "view)")
        branches = ([args.branch] if args.branch else []) + args.refspecs
        tags = args.tags or []
        if not branches and not tags:
            raise SystemExit(f"{args.cmd}: name at least one branch "
                             "(--branch or positional) or --tags")

        def _tracking_name(spec):
            return spec if "/" not in spec else "origin"

        try:
            if len(remote_specs) > 1:
                # multi-remote push: shared fetch side, N destinations
                remotes = [(_tracking_name(spec),
                            _resolve_remote(lake, spec))
                           for spec in remote_specs]
                reports = sync_mod.push_fanout(
                    lake.store, remotes, branches, tags=tags,
                    force=args.force,
                    cache_entries=not args.no_cache_entries,
                    runs=not args.no_runs, jobs=args.jobs)
                for name, rep in reports:
                    print(f"{name}: {rep.summary()}")
                return
            spec = remote_specs[0]
            remote = _resolve_remote(lake, spec)
            kw = dict(remote_name=_tracking_name(spec), force=args.force,
                      cache_entries=not args.no_cache_entries,
                      runs=not args.no_runs, jobs=args.jobs)
            if (len(branches) == 1 and not tags
                    and not any(ch in branches[0] for ch in "*?[")):
                # single literal branch: the PR-2 surface, unchanged output
                fn = sync_mod.push if args.cmd == "push" else sync_mod.pull
                rep = fn(lake.store, remote, branches[0], **kw)
            else:
                fn = (sync_mod.push_refs if args.cmd == "push"
                      else sync_mod.pull_refs)
                rep = fn(lake.store, remote, branches, tags=tags, **kw)
        except SyncError as e:
            raise SystemExit(str(e)) from None
        print(rep.summary())


if __name__ == "__main__":
    main()
