"""Serving cells: the program's ``BatchedServer`` (continuous batching over
a ``ServeEngine``) under open-loop arrivals.

  set-up   weights made on the device from the seed in one jitted call;
           one request per prompt length of the mix, which compiles (or
           loads) the admit path at each length, the slot write and the
           per-row decode; the token log's host copies
  window   requests are submitted when due (or at the first chance after)
           and ``step()`` runs while any is pending; after each step one
           host copy of that interval's token vector stamps the tokens it
           carries, as a streaming front end must.  A request's first
           token arrives with the interval that admitted it
  drain    no new arrivals; serving goes on until every request due in the
           window has finished, or ``drain_s`` passes after the trace is
           written (those left count as failed)
  check    a sample of finished requests, the longest among them, against
           the plain reference: the widest gap by which a served token's
           logit lies below the reference's best at its position
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

import generators
import harness
from harness import Check, log

TRACE_SPANS = ("admit", "sync", "idle_wait")
#: control readings besides the program's (``check.py`` sets ("fp8",))
EXTRA: tuple = ()


@dataclasses.dataclass
class Flow:
    """One request as the front end sees it."""
    req: generators.Request
    submitted: float = None
    tokens: List[float] = dataclasses.field(default_factory=list)  # receipt
    decodes: int = 0


def warm_up(jax, server, mix, vocab: int):
    """Compile or load every program the window will run: the admit path
    at each prompt length, the slot write, the decode, and the token
    log's concatenations of 1 .. 16 intervals."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for i, plen in enumerate(sorted(int(k) for k in mix["prompt_len"])):
        server.submit(-1 - i, rng.integers(3, vocab, plen).astype(np.int32), 2)
    while server.pending:
        server.step()
    for k in range(1, 17):
        np.asarray(jnp.concatenate([server._tokens] * k, axis=1))
    server.completed.clear()


def run(cell, seed: int, seconds: float, profiler, spans, compiles,
        devices, t_start: float, limits: dict) -> harness.Outcome:
    import jax

    from repro.models import init_params
    from repro.serving import BatchedServer, ServeEngine

    cfg = cell.model_config()
    mix = cell.traffic
    init_seed = generators.sub_seed(seed, "init")
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(init_seed))
    engine = ServeEngine(cfg, params, max_len=mix["max_len"],
                         batch_size=mix["slots"])
    server = BatchedServer(engine)
    warm_up(jax, server, mix, cfg.vocab_size)
    prefill_one, write_slot = engine.prefill_one, engine.write_slot

    def admit_prefill(prompt):
        with spans.span("admit"):
            return prefill_one(prompt)

    def admit_write(*a):
        with spans.span("admit"):
            return write_slot(*a)

    engine.prefill_one, engine.write_slot = admit_prefill, admit_write

    reqs = [r for r in generators.chat_schedule(mix, cfg.vocab_size, seed,
                                                seconds) if r.due < seconds]
    flows: Dict[int, Flow] = {r.rid: Flow(r) for r in reqs}
    intervals = []  # (t_sync, live positions of the rows decoded)
    queued: set = set()
    nxt = 0
    profiler.start()
    t0 = profiler.t0
    t_close, t_drain = t0 + seconds, None
    while True:
        now = time.perf_counter()
        if now >= t_close:
            if t_drain is None:  # writing the trace takes its own time
                profiler.stop()
                now = time.perf_counter()
                t_drain = now + mix["drain_s"]
            unfinished = [f for f in flows.values()
                          if len(f.tokens) < f.req.n_out]
            if not unfinished or now >= t_drain:
                break
        while nxt < len(reqs) and t0 + reqs[nxt].due <= now:
            r = reqs[nxt]
            server.submit(r.rid, r.prompt, r.n_out)
            flows[r.rid].submitted = time.perf_counter()
            queued.add(r.rid)
            nxt += 1
        if not server.pending:
            wait = (t0 + reqs[nxt].due if nxt < len(reqs) else t_close) - now
            if wait > 0:
                with spans.span("idle_wait"):
                    time.sleep(wait)
            continue
        done_before = set(server.completed)
        server.step()
        with spans.span("sync"):
            np.asarray(server._tokens)
        t_sync = time.perf_counter()
        waiting = {q.request_id for q in server.queue}
        admitted = {r for r in queued if r not in waiting}
        queued -= admitted
        decoded = ({inf.request.request_id for inf in server._active.values()}
                   | (set(server.completed) - done_before))
        live = []
        for rid in decoded:
            f = flows[rid]
            live.append(len(f.req.prompt) + f.decodes)
            f.decodes += 1
            f.tokens.extend([t_sync] * (2 if rid in admitted else 1))
        intervals.append((t_sync, live))
    t_end = time.perf_counter()

    due = list(flows.values())
    ttft, gaps, lags, failed = [], [], [], 0
    for f in due:  # TTFT and the front end's lag are logged, not bounded

        if f.tokens:
            ttft.append(f.tokens[0] - (t0 + f.req.due))
        else:
            ttft.append(t_end - (t0 + f.req.due))
        if len(f.tokens) < f.req.n_out:
            failed += 1
        lags.append(f.submitted - (t0 + f.req.due))
        got = [t for t in f.tokens if t <= t_close]
        gaps.extend(np.diff(got).tolist())
    e2e = {"tbt_p95_ms": harness.percentile(gaps, 95) * 1e3,
           "setup_s": t0 - t_start}
    peak = harness.memory_peak(devices)
    log(f"setup {e2e['setup_s']:.2f} s; window: {len(due)} requests, "
        f"{len(intervals)} intervals, "
        f"{failed} unfinished; compiles in the window: "
        f"{compiles.between(t0, t_close)}; drain {t_end - t_close:.2f} s; "
        f"{len(gaps)} gaps; submit lag p95 "
        f"{harness.percentile(lags, 95) * 1e3:.2f} ms; ttft_ms " + " ".join(
            f"p{q} {harness.percentile(ttft, q) * 1e3:.2f}"
            for q in (50, 75, 90, 95, 99)) + "; tbt_ms " + " ".join(
            f"p{q} {harness.percentile(gaps, q) * 1e3:.2f}"
            for q in (50, 75, 90, 95, 99)))

    record = harness.Record(
        cell=cell, cfg=cfg, peaks=None, window=(t0, t_close), spans=spans,
        counters={"intervals": [(t, live) for t, live in intervals
                                if t <= t_close],
                  "requests": len(due)})

    sample = choose_sample(server, flows, mix["check_requests"], seed)
    del engine, server, params, prefill_one, write_slot
    gc.collect()
    checks = compare(cell, init_seed, sample, limits, ("f32", *EXTRA))
    checks.append(Check("unfinished", float(failed), 0.0))
    return harness.Outcome(e2e=e2e, record=record, checks=checks,
                           attempted=len(due), failed=failed,
                           memory_peak_bytes=peak)


def choose_sample(server, flows, n: int, seed: int):
    """The longest finished request and others drawn from the seed:
    (prompt, served tokens) pairs, copied to the host."""
    done = sorted(rid for rid in server.completed if rid in flows)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(server.completed[r].tokens[0]), -r))
    rng = np.random.default_rng(generators.sub_seed(seed, "sample"))
    rest = [r for r in done if r != longest]
    pick = [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [(flows[r].req.prompt, np.asarray(server.completed[r].tokens[0]))
            for r in pick]


def gaps_of(reference, sz, w, sample, max_len: int, dtypes=("f32",)):
    """Per dtype: the widest float32-reference gap (best logit minus the
    chosen token's) over every served position of the sample.  For "f32"
    the chosen token is the served one; for another dtype it is the token
    that dtype's own forward puts first (the control's reading).
    ``reference`` is the configuration's reference module."""
    out = {d: 0.0 for d in dtypes}
    for prompt, served in sample:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        pos = np.arange(len(prompt) - 1, len(seq))
        ref = np.asarray(reference.forward_logits(sz, w, seq, max_len))[pos]
        best = ref.max(axis=-1)
        for d in dtypes:
            if d == "f32":
                chosen = served
            else:
                low = np.asarray(reference.forward_logits(sz, w, seq, max_len,
                                                          d))[pos]
                chosen = low.argmax(axis=-1)
            g = best - ref[np.arange(len(pos)), chosen]
            out[d] = max(out[d], float(g.max()))
    return out


def compare(cell, init_seed, sample, limits, dtypes=("f32",)) -> List[Check]:
    if not sample:
        return [Check("served_gap", float("inf"), limits["served_gap"])]
    reference = cell.reference()
    sz = reference.Sizes.from_file(cell.config)
    t0 = time.perf_counter()
    w = reference.init_weights(sz, init_seed)
    gaps = gaps_of(reference, sz, w, sample, cell.traffic["max_len"], dtypes)
    n_tok = sum(len(s) for _, s in sample)
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t0:.1f} s; widest gaps {gaps}")
    checks = [Check("served_gap", gaps["f32"], limits["served_gap"])]
    for d in dtypes:
        if d != "f32":
            checks.append(Check(f"served_gap.{d}", gaps[d],
                                limits["served_gap"]))
    return checks
