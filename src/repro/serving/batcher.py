"""Continuous batching: admit new requests into in-flight decode batches.

The scheduler keeps a fixed pool of decode *slots* over one shared KV/state
cache.  Each :meth:`ContinuousBatcher.step` is one **decode interval**:

1. *admit* — every free slot pulls the next queued request: the prompt is
   prefilled alone at its exact length (one B=1 prefill, same computation as
   serving the request solo) and its cache rows are written into the slot;
2. *decode* — ONE vmapped decode call advances every slot a token, each row
   against its own cache at its own position (``ServeEngine.row_decode``);
3. *retire* — slots that produced their last token complete their request
   and free up, to be refilled at the next step's admit phase.

The decode loop never blocks on the device: retirement depends only on
token COUNTS, so each interval's token vector stays on device in an
interval log and dispatches queue asynchronously; values are materialized
(one host sync) when a request completes.  Free slots keep decoding
garbage rows — their outputs are never read and their cache rows are
overwritten at the next admit, which costs nothing extra because the
vmapped call advances all ``slots`` rows either way.

Two contracts distinguish this from the :class:`FixedBatchedServer` it
subsumes, both pinned by ``tests/serving_conformance.py``:

* **equivalence** — a request's token stream is bit-identical to generating
  it alone (per-slot isolation: exact-length prefill + per-row decode), for
  any arrival order / ``n_tokens`` mix;
* **no head-of-line blocking** — a long request occupies one slot; requests
  submitted later flow through the other slots and complete on their own
  schedule instead of waiting for the longest batch-mate.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..models import init_cache
from .engine import GenerationResult, Request, ServeEngine


@dataclass
class _InFlight:
    request: Request
    first_tok: jnp.ndarray  # device scalar until completion materializes it
    ivals: List[int] = field(default_factory=list)  # decode intervals used


class ContinuousBatcher:
    """Slot-scheduled continuous-batching server over a :class:`ServeEngine`.

    Same surface as the fixed server (``submit`` / ``step`` / ``queue`` /
    ``completed``) plus ``pending`` (queued + in-flight) — drive with
    ``while server.pending: server.step()``.
    """

    def __init__(self, engine: ServeEngine, *, slots: Optional[int] = None):
        self.engine = engine
        self.slots = int(slots or engine.batch_size)
        cfg = engine.cfg
        self.queue: List[Request] = []
        self.completed: Dict[int, GenerationResult] = {}
        self._active: Dict[int, _InFlight] = {}      # slot -> in-flight
        self._free: List[int] = list(range(self.slots))
        self._cache = init_cache(cfg, self.slots, engine.max_len,
                                 dtype=cfg.compute_dtype)
        self._cache["pos"] = jnp.zeros((self.slots,), jnp.int32)  # per-row
        self._tokens = jnp.zeros((self.slots, 1), jnp.int32)
        self._decode = engine.row_decode()
        self._log: Dict[int, jnp.ndarray] = {}       # interval -> (S,1) toks
        self._np_log: Dict[int, np.ndarray] = {}     # ...materialized
        self._submitted: Dict[int, float] = {}       # request id -> when
        #: decode intervals run so far (the bench's unit of rollout blip)
        self.intervals = 0

    # ------------------------------------------------------------- surface
    def submit(self, request_id: int, prompt: np.ndarray, n_tokens: int):
        assert n_tokens >= 1
        assert prompt.shape[0] + n_tokens <= self.engine.max_len
        self.queue.append(Request(request_id, np.asarray(prompt, np.int32),
                                  n_tokens))
        self._submitted[request_id] = time.perf_counter()

    @property
    def pending(self) -> int:
        """Requests not yet completed (waiting + in a slot)."""
        return len(self.queue) + len(self._active)

    def cancel_all(self) -> List[Request]:
        """Abandon queued AND in-flight work, handing the requests back for
        re-dispatch (generation is deterministic, so a re-run elsewhere
        produces the identical tokens) — the fleet's crash path."""
        out = list(self.queue)
        self.queue = []
        for slot in sorted(self._active):
            out.append(self._active[slot].request)
        self._active.clear()
        self._free = list(range(self.slots))
        self._log.clear()
        self._np_log.clear()
        self._submitted.clear()
        return out

    # ---------------------------------------------------------------- step
    _CHUNK = 16  # intervals materialized per host transfer

    def _tok(self, interval: int, slot: int) -> int:
        """Token decoded for ``slot`` at ``interval`` — materialized lazily
        on first read, a CHUNK of consecutive intervals per host transfer
        (per-interval np.asarray costs a dispatch each; one concatenated
        copy amortizes it across every slot retiring nearby)."""
        a = self._np_log.get(interval)
        if a is None:
            lo = interval - interval % self._CHUNK
            span = [j for j in range(lo, lo + self._CHUNK) if j in self._log]
            with tracing.span("serve.fetch", intervals=len(span)):
                block = np.asarray(jnp.concatenate(
                    [self._log[j] for j in span], axis=1))
            for col, j in enumerate(span):
                self._np_log[j] = block[:, col:col + 1]
            a = self._np_log[interval]
        return int(a[slot, 0])

    def _complete(self, inflight: _InFlight, slot: Optional[int]) -> None:
        r = inflight.request
        toks = [int(inflight.first_tok)] + [self._tok(j, slot)
                                            for j in inflight.ivals]
        self.completed[r.request_id] = GenerationResult(
            tokens=np.asarray(toks, np.int32)[None, :],
            model_commit=self.engine.model_commit,
            prompt_len=r.prompt.shape[0])

    def _prune_log(self) -> None:
        if not self._log:
            return
        floor = (min(min(inf.ivals, default=self.intervals + 1)
                     for inf in self._active.values())
                 if self._active else self.intervals + 1)
        for j in [j for j in self._log if j < floor]:
            self._log.pop(j)
            self._np_log.pop(j, None)

    def _admit(self) -> int:
        done = 0
        while self._free and self.queue:
            r = self.queue.pop(0)
            with tracing.span("serve.admit", rid=r.request_id,
                              prompt_len=int(r.prompt.shape[0])) as s:
                submitted = self._submitted.pop(r.request_id, None)
                if submitted is not None:  # the wait in the queue
                    tracing.record("serve.queue", submitted, s.t0,
                                   rid=r.request_id)
                first_tok, cache1 = self.engine.prefill_one(r.prompt)
                inflight = _InFlight(r, first_tok)
                if r.n_tokens == 1:  # completed at prefill; slot stays free
                    self._complete(inflight, None)
                    done += 1
                    continue
                slot = self._free.pop(0)
                self._cache, self._tokens = self.engine.write_slot(
                    self._cache, self._tokens, cache1, first_tok, slot)
                self._active[slot] = inflight
        return done

    def step(self) -> int:
        """One admit + decode interval; returns requests completed."""
        with tracing.span("serve.step") as s:
            done = self._admit()
            s.set(active=len(self._active))
            if not self._active:
                return done
            self.intervals += 1
            s.set(interval=self.intervals)
            with tracing.span("serve.decode"):
                self._tokens, self._cache = self._decode(
                    self.engine.params, self._tokens, self._cache)
            self._log[self.intervals] = self._tokens
            for slot in sorted(self._active):
                inflight = self._active[slot]
                inflight.ivals.append(self.intervals)
                if 1 + len(inflight.ivals) >= inflight.request.n_tokens:
                    self._complete(inflight, slot)
                    del self._active[slot]
                    self._free.append(slot)
                    done += 1
            if done or not self.intervals % 64:
                self._prune_log()
            return done


class BatchedServer(ContinuousBatcher):
    """The request server, now continuously batched.

    The fixed-bucket scheduler this name used to denote (and its
    head-of-line blocking) lives on as
    :class:`~repro.serving.engine.FixedBatchedServer`, kept as the
    benchmark baseline; existing call sites get continuous batching."""
