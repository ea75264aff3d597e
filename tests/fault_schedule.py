"""Deterministic fault injection for the storage stack.

The gc-vs-push race (and every other concurrency contract in the sync
layer) is only testable if interleavings can be *scheduled*, not hoped
for.  This module provides that instrument:

``Schedule``
    Maps named **sync points** to actions.  A sync point is
    ``"<op>:before"`` / ``"<op>:after"`` (store wrapper) or
    ``"wire:<op>:before"`` / ``"wire:<op>:after"`` (transport wrapper).
    Actions: **gate** (block the arriving thread until the test releases
    it — how a push is frozen between its uploads and its ``cas_refs``),
    **kill** (raise :class:`InjectedFault`, a ``RemoteError`` subclass —
    at ``:before`` the request was never delivered, at ``:after`` it was:
    the ambiguous case), **delay** (sleep — reorders concurrent ops).

``SeededSchedule``
    Randomized fuzzing with *positional determinism*: the decision for
    the N-th arrival at a sync point is drawn from
    ``Random(f"{seed}:{point}:{n}")`` — independent of thread timing, so
    a seed names a reproducible fault pattern even under a racy
    interleaving.  Every decision is logged; :meth:`SeededSchedule.to_json`
    dumps the pattern for the CI failure artifact.

``FaultyStore`` / ``FaultyTransport``
    Transparent wrappers over any ``StoreBackend`` / transport that fire
    the schedule around each intercepted operation.

Used by tests/test_gc_race.py (deterministic gc-vs-push interleavings)
and the seeded-fuzz leg of tests/sync_conformance.py.
"""

from __future__ import annotations

import json
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import msgpack

from repro.core.errors import RemoteError


class InjectedFault(RemoteError):
    """A scheduled fault — distinguishable from real transport errors."""


class Gate:
    """A pause point: the arriving thread sets ``reached`` and blocks on
    ``release``.  Tests wait for ``reached`` (the op is now frozen at the
    sync point), interleave whatever they want, then ``release.set()``."""

    def __init__(self, point: str):
        self.point = point
        self.reached = threading.Event()
        self.release = threading.Event()

    def wait_reached(self, timeout: float = 30.0) -> None:
        if not self.reached.wait(timeout):
            raise AssertionError(
                f"no thread arrived at sync point {self.point!r} "
                f"within {timeout}s")

    def open(self) -> None:
        self.release.set()


class Schedule:
    """Explicit, programmable fault schedule (deterministic tests).

    Rules are registered per sync point with an optional 1-based
    ``occurrence`` (None = every arrival).  Thread-safe; arrival counts
    are per point.
    """

    _GATE_TIMEOUT = 60.0

    def __init__(self):
        self._rules: Dict[str, List[Tuple[Optional[int], Tuple]] ] = {}
        self._counts: Dict[str, int] = {}
        self._lock = threading.Lock()
        #: every arrival that triggered an action: (point, n, action)
        self.log: List[Tuple[str, int, str]] = []

    # ------------------------------------------------------------- rules
    def _add(self, point: str, action: Tuple,
             occurrence: Optional[int]) -> None:
        with self._lock:
            self._rules.setdefault(point, []).append((occurrence, action))

    def gate(self, point: str, *, occurrence: Optional[int] = 1) -> Gate:
        """Freeze the ``occurrence``-th arrival at ``point`` until the
        returned :class:`Gate` is opened."""
        g = Gate(point)
        self._add(point, ("gate", g), occurrence)
        return g

    def kill(self, point: str, *, occurrence: Optional[int] = 1,
             times: int = 1) -> "Schedule":
        """Raise :class:`InjectedFault` at ``point`` (``times`` arrivals
        starting from ``occurrence``; with ``occurrence=None`` every
        arrival dies)."""
        if occurrence is None:
            self._add(point, ("kill",), None)
        else:
            for i in range(times):
                self._add(point, ("kill",), occurrence + i)
        return self

    def delay(self, point: str, seconds: float, *,
              occurrence: Optional[int] = None) -> "Schedule":
        self._add(point, ("delay", seconds), occurrence)
        return self

    # ----------------------------------------------------------- firing
    def _actions_for(self, point: str) -> Tuple[int, List[Tuple]]:
        with self._lock:
            n = self._counts[point] = self._counts.get(point, 0) + 1
            actions = [a for occ, a in self._rules.get(point, ())
                       if occ is None or occ == n]
            for a in actions:
                self.log.append((point, n, a[0]))
            return n, actions

    def fire(self, point: str) -> None:
        """Called by the wrappers at every sync point.  Applies matching
        actions in registration order; ``kill`` raises."""
        _n, actions = self._actions_for(point)
        for action in actions:
            if action[0] == "gate":
                g: Gate = action[1]
                g.reached.set()
                if not g.release.wait(self._GATE_TIMEOUT):
                    raise AssertionError(
                        f"gate at {point!r} never released "
                        f"({self._GATE_TIMEOUT}s)")
            elif action[0] == "delay":
                time.sleep(action[1])
            elif action[0] == "kill":
                raise InjectedFault(f"injected fault at {point!r}")


class SeededSchedule(Schedule):
    """Randomized schedule with positionally deterministic decisions.

    The N-th arrival at sync point P draws from
    ``Random(f"{seed}:{P}:{N}")`` — thread timing cannot change what a
    given (point, arrival) does, so ``seed`` fully names the fault
    pattern.  ``kill_points``/``delay_points`` are substring filters over
    sync-point names (e.g. ``"wire:"`` faults only the transport layer;
    ``"cas_refs"`` only ref updates).
    """

    def __init__(self, seed: int, *, p_kill: float = 0.04,
                 p_delay: float = 0.35, max_delay: float = 0.002,
                 kill_points: Tuple[str, ...] = (":before",),
                 delay_points: Tuple[str, ...] = ("",),
                 max_kills_per_point: int = 2):
        super().__init__()
        self.seed = seed
        self.p_kill = p_kill
        self.p_delay = p_delay
        self.max_delay = max_delay
        self.kill_points = kill_points
        self.delay_points = delay_points
        # cap consecutive kills so a retrying client (retries=2) always
        # gets through eventually: fuzzing probes interleavings, it must
        # not starve every operation into permanent failure
        self.max_kills_per_point = max_kills_per_point
        self._kills: Dict[str, int] = {}
        self.decisions: List[Dict[str, Any]] = []

    def fire(self, point: str) -> None:
        with self._lock:
            n = self._counts[point] = self._counts.get(point, 0) + 1
        rng = random.Random(f"{self.seed}:{point}:{n}")
        roll = rng.random()
        may_kill = (any(k in point for k in self.kill_points)
                    and self._kills.get(point, 0)
                    < self.max_kills_per_point)
        if may_kill and roll < self.p_kill:
            with self._lock:
                self._kills[point] = self._kills.get(point, 0) + 1
                self.decisions.append(
                    {"point": point, "n": n, "action": "kill"})
            raise InjectedFault(
                f"injected fault at {point!r} (seed {self.seed}, "
                f"arrival {n})")
        if (any(d in point for d in self.delay_points)
                and roll < self.p_kill + self.p_delay):
            delay = rng.random() * self.max_delay
            with self._lock:
                self.decisions.append(
                    {"point": point, "n": n, "action": "delay",
                     "seconds": delay})
            time.sleep(delay)

    def to_json(self) -> str:
        """The decision log as a replay artifact (uploaded by the CI
        gc-race job on failure: the seed reproduces the run, the log
        shows what it did)."""
        with self._lock:
            return json.dumps({"seed": self.seed,
                               "p_kill": self.p_kill,
                               "p_delay": self.p_delay,
                               "max_delay": self.max_delay,
                               "decisions": list(self.decisions)},
                              indent=2)


# ------------------------------------------------------------------ wrappers
#: StoreBackend methods wrapped with sync points.  Anything not listed
#: (root, _supports_encoded, gc_mark, ...) passes through untouched.
INTERCEPTED_OPS = (
    "put", "put_parts", "put_many", "put_encoded", "put_many_encoded",
    "get", "get_many", "get_encoded", "get_many_encoded",
    "has", "has_many", "size", "mtime", "delete_object",
    "set_ref", "get_ref", "cas_ref", "cas_refs", "delete_ref",
    "list_refs", "list_objects",
)
#: ops that fire another op's sync points: ``put_parts`` is ``put`` of the
#: joined parts, so a seed names the same fault pattern whichever of the
#: two a caller uses
SYNC_POINT_OF = {"put_parts": "put"}


class FaultyStore:
    """A ``StoreBackend`` whose intercepted operations fire
    ``"<op>:before"`` / ``"<op>:after"`` on a :class:`Schedule`.

    Wraps *any* backend (filesystem, ``RemoteStore``, ``S3Backend``), so
    the same schedule drives races through every transport the
    conformance matrix covers.
    """

    def __init__(self, inner, schedule: Schedule):
        object.__setattr__(self, "inner", inner)
        object.__setattr__(self, "schedule", schedule)

    def __getattr__(self, name: str):
        attr = getattr(self.inner, name)
        if name in INTERCEPTED_OPS and callable(attr):
            schedule = self.schedule

            def wrapped(*args, _attr=attr,
                        _name=SYNC_POINT_OF.get(name, name), **kwargs):
                schedule.fire(f"{_name}:before")
                out = _attr(*args, **kwargs)
                schedule.fire(f"{_name}:after")
                return out

            return wrapped
        return attr


class FaultyTransport:
    """A transport wrapper firing ``"wire:<op>:before"`` / ``":after"``.

    A kill at ``:before`` drops the request un-delivered (clean retryable
    failure); a kill at ``:after`` drops the *reply* after the server
    applied the request — the ambiguous case the sync layer resolves by
    re-reading refs."""

    def __init__(self, inner, schedule: Schedule):
        self.inner = inner
        self.schedule = schedule

    def request(self, payload: bytes) -> bytes:
        try:
            op = msgpack.unpackb(payload, raw=False).get("op", "?")
        except Exception:  # noqa: BLE001 - never block on a weird frame
            op = "?"
        self.schedule.fire(f"wire:{op}:before")
        reply = self.inner.request(payload)
        self.schedule.fire(f"wire:{op}:after")
        return reply

    def close(self) -> None:
        self.inner.close()
