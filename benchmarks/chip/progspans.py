"""The program's own spans (``repro.tracing``), as the per-layer readers
take them: the spans that start inside a run's window, each with its
subtree, and the same spans placed on a device trace's clock.

A program without ``repro.tracing``, or one whose bounded buffer no longer
reaches back to the window's start, gives nothing, and each reader then
returns None.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: the commit's stages: span name -> metric suffix (``commit_<suffix>_s``)
STAGES = {"ckpt.copy": "copy", "table.encode": "encode",
          "store.hash": "hash", "store.compress": "compress",
          "store.write": "write", "ckpt.digest": "digest"}


def buffer(rec) -> Optional[list]:
    """Every span the program kept, or None: no tracing module, or the
    buffer's oldest span closed after the window opened (so spans of the
    window may have been dropped)."""
    try:
        from repro import tracing
    except ImportError:
        return None
    spans = tracing.spans()
    if not spans or spans[0].t1 > rec.window[0]:
        return None
    return spans


def window(rec) -> Optional[Tuple[list, Dict[int, list]]]:
    """(spans that start in the window, children of every kept span by
    parent id), or None."""
    spans = buffer(rec)
    if spans is None:
        return None
    lo, hi = rec.window
    kids: Dict[int, list] = defaultdict(list)
    for s in spans:
        if s.parent is not None:
            kids[s.parent].append(s)
    return [s for s in spans if lo <= s.t0 < hi], kids


def topmost(span, kids, names) -> List:
    """The spans of ``span``'s subtree named in ``names`` that have no such
    span above them inside the subtree."""
    out, todo = [], list(kids[span.id])
    while todo:
        s = todo.pop()
        if s.name in names:
            out.append(s)
        else:
            todo.extend(kids[s.id])
    return out


def duration(spans) -> float:
    return sum(s.t1 - s.t0 for s in spans)


def commits(rec) -> Optional[List[Dict[str, float]]]:
    """Per ``ckpt.save`` that began in the window, seconds per stage and
    ``other``: the save's time outside every stage span of its subtree.
    The parts of one commit sum to its ``ckpt.save`` time."""
    got = window(rec)
    if got is None:
        return None
    spans, kids = got
    out = []
    for save in spans:
        if save.name != "ckpt.save":
            continue
        parts = dict.fromkeys([*STAGES.values(), "other"], 0.0)
        for s in topmost(save, kids, STAGES):
            parts[STAGES[s.name]] += s.t1 - s.t0
        parts["other"] = (save.t1 - save.t0) - sum(parts.values())
        out.append(parts)
    return out or None


def commit_stage(rec, part: str) -> Optional[float]:
    """Mean seconds of one stage (or ``other``) per commit in the window."""
    per = commits(rec)
    if not per:
        return None
    return sum(p[part] for p in per) / len(per)


def loop_host_ms(rec, step: str, wait: str) -> Optional[float]:
    """Mean milliseconds, per ``step`` span wholly inside the window and
    ended without an error, of its time outside its ``wait`` spans."""
    got = window(rec)
    if got is None:
        return None
    spans, kids = got
    hi = rec.window[1]
    host = [(s.t1 - s.t0) - duration(topmost(s, kids, (wait,)))
            for s in spans
            if s.name == step and s.t1 <= hi and s.error is None]
    if not host:
        return None
    return sum(host) / len(host) * 1e3


def on_trace(rec, spans) -> List[Tuple[int, int]]:
    """``spans`` as intervals on the device trace's clock, anchored at the
    window's start: trace_ns = trace_window[0] + (t - window[0]) * 1e9."""
    lo, w0 = rec.trace_window[0], rec.window[0]
    return [(lo + int(round((s.t0 - w0) * 1e9)),
             lo + int(round((s.t1 - w0) * 1e9))) for s in spans]


def idle_untraced(rec) -> Optional[float]:
    """Percent of the traced window in which the device runs no operation
    and no span is open, the program's or the benchmark's."""
    import devtrace as tr

    spans = buffer(rec)
    if spans is None:
        return None
    lo, hi = rec.trace_window
    cover = list(rec.trace.busy(rec.device))
    for ivs in rec.trace.host.values():
        cover.extend(ivs)
    cover.extend(on_trace(rec, spans))
    return 100.0 * tr.total(tr.gaps(cover, lo, hi)) / (hi - lo)
