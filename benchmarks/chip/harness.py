"""Pieces every cell's run shares: the device check, compile counting, host
spans, the profiler window and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL = "cache_retrieval"


class NoChip(RuntimeError):
    pass


def require_chips(jax, chips: int) -> list:
    """The run's devices; no TPU, or fewer chips than the cell asks for, is
    an error (never a fallback to the CPU)."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs[:chips]


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at a fixed directory inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    from spec import ROOT

    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Compiles:
    """Times of JAX's compile and cache-load events, from its monitoring
    hooks, so a run can count those that fell inside its window."""

    def __init__(self, jax):
        self.events: List[Tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE or CACHE_RETRIEVAL in event:
            self.events.append((time.perf_counter(), event, duration))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.events if t0 <= t <= t1)


class Spans:
    """Host spans around the program's calls, on the perf_counter clock,
    written into the profiler's trace as well while it records."""

    def __init__(self, jax):
        self._annotate = jax.profiler.TraceAnnotation
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = self._annotate(name) if self.tracing else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ctx:
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def within(self, name: str, t0: float, t1: float):
        return [(s, e) for s, e in self.spans.get(name, []) if t0 <= s < t1]


class Profiler:
    """The device trace of the measured window (``--trace 1``)."""

    def __init__(self, jax, enabled: bool, spans: Spans):
        self.jax, self.enabled, self.spans = jax, enabled, spans
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_") if enabled \
            else None
        self.t0 = self.t1 = None
        self._window = None

    def start(self):
        """Open the window: a host span named ``window`` marks it on the
        trace's clock."""
        if self.enabled:
            self.jax.profiler.start_trace(self.dir)
            self.spans.tracing = True
            self._window = self.jax.profiler.TraceAnnotation("window")
            self._window.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        if self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        if self.enabled:
            self._window.__exit__(None, None, None)
            self.spans.tracing = False
            self.jax.profiler.stop_trace()

    def load(self, host_spans):
        """(trace, (lo, hi) of the window on the trace's clock)."""
        import devtrace as tr

        t = tr.load(tr.find_xplane(self.dir), ["window", *host_spans])
        (lo, hi), = t.host.pop("window")
        return t, (lo, hi)

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def emit(result: dict, checks: List[Check]) -> None:
    """The checks as the last lines of stderr, then the result line (with
    the checks under the last key) as the last line of stdout."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Record:
    """What a run leaves for the per-layer metrics' readers."""
    cell: object                  # spec.Cell
    cfg: object                   # the program's ModelConfig
    peaks: dict
    window: Tuple[float, float]   # perf_counter seconds
    spans: Spans
    counters: dict
    trace: Optional[object] = None       # devtrace.Trace (``--trace 1``)
    trace_window: Optional[Tuple[int, int]] = None  # the window, trace clock
    device: Optional[str] = None         # the traced device's plane name

    def busy_s(self) -> float:
        import devtrace as tr

        lo, hi = self.trace_window
        return tr.total(tr.clip(self.trace.busy(self.device), lo, hi)) / 1e9

    def window_s(self) -> float:
        lo, hi = self.trace_window
        return (hi - lo) / 1e9

    def modules(self, name_part: str):
        """Executables named ``jit_<name_part>...`` inside the window."""
        lo, hi = self.trace_window
        return [e for e in self.trace.module_events(name_part, self.device)
                if lo <= e.start < hi]


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]
    record: Record
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
