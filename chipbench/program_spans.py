"""The program's own host spans (``hhzs:*``, ``repro.obs.spans``) in a
profiler trace, beside the harness's (``cb:*``).

- ``load_events``: what ``trace.load_events`` keeps, plus the program's
  host spans;
- ``span_times``: per span name, its count, total and self seconds on the
  host thread that holds ``cb:window``, clipped to the window.  Self time
  is a span's time minus what its child spans on that thread cover, so
  the self time of ``cb:window`` is the host time in no span at all;
- ``summarize``: ``trace.reduce_events``'s summary with every key as it
  reads there, plus ``spans`` from ``span_times``, except ``idle_gaps``:
  each idle gap of the device is split over the innermost spans of either
  kind that the host was in during it (harness spans without their
  ``cb:``, program spans with their ``hhzs:``, ``host outside spans``
  where it was in none), where ``trace.py`` gives a whole gap to the
  harness span around its middle.  So the idle time outside every span is
  never more than the self time of ``cb:window``.

``trace.py`` does not call this module yet, so the cells' ``--trace 1``
runs do not read program spans; ``chipbench/trace_program.py`` runs a cell
with it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

from chipbench import trace

PROGRAM_PREFIX = "hhzs:"
OUTSIDE = "host outside spans"
HOST_PREFIXES = (trace.SPAN_PREFIX, PROGRAM_PREFIX)


def load_events(trace_dir: str) -> List[trace.Event]:
    """Events of the newest xplane file under ``trace_dir``: device planes'
    module and op lines, and the host spans of the harness and the
    program."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: List[trace.Event] = []
    for plane in pd.planes:
        device = trace.is_device_plane(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (trace.MODULE_LINE, trace.OPS_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIXES):
                    continue
                out.append(trace.Event(plane.name, line.name, ev.name,
                                       float(ev.start_ns),
                                       float(ev.duration_ns)))
    return out


def _window(events: List[trace.Event]) -> trace.Event:
    windows = [e for e in events if e.name == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {trace.WINDOW_SPAN} span")
    return windows[0]


def _thread_spans(events: List[trace.Event]):
    """The window and the spans on its host thread other than the window,
    clipped to it, as (start, end, name) sorted outer before inner."""
    w = _window(events)
    lo, hi = w.start_ns, w.start_ns + w.dur_ns
    spans = []
    for e in events:
        if (e.plane, e.line) != (w.plane, w.line) or e is w \
                or not e.name.startswith(HOST_PREFIXES):
            continue
        c = trace._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if c is not None:
            spans.append((c[0], c[1], e.name))
    spans.sort(key=lambda s: (s[0], -s[1]))
    return w, lo, hi, spans


def _innermost(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into (start, end, name) pieces, each named by the
    innermost span open in it, or ``OUTSIDE`` where none is.  Spans on one
    thread nest, so a stack of open spans gives the innermost one."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    t = lo

    def upto(x, name):
        nonlocal t
        if x > t:
            pieces.append((t, x, name))
            t = x

    for a, b, name in spans:
        while stack and stack[-1][0] <= a:
            upto(*stack.pop())
        upto(a, stack[-1][1] if stack else OUTSIDE)
        stack.append((b, name))
    while stack:
        upto(*stack.pop())
    upto(hi, OUTSIDE)
    return pieces


def span_times(events: List[trace.Event]) -> Dict[str, Dict]:
    """{name: {count, total_s, self_s}} of the spans on the window's host
    thread, clipped to the window, ``cb:window`` included: its self time
    is the time in no other span."""
    w, lo, hi, spans = _thread_spans(events)
    out: Dict[str, Dict] = defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    for a, b, name in spans + [(lo, hi, w.name)]:
        out[name]["count"] += 1
        out[name]["total_s"] += (b - a) * 1e-9
    for a, b, name in _innermost(spans, lo, hi):
        out[w.name if name == OUTSIDE else name]["self_s"] += (b - a) * 1e-9
    return dict(out)


def _idle_gaps(events: List[trace.Event], lo: float,
               hi: float) -> List[Tuple[float, float]]:
    """(start, end) of each device's idle gaps inside [lo, hi]."""
    busy: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    lines: Dict[str, set] = defaultdict(set)
    for e in events:
        if trace.is_device_plane(e.plane):
            lines[e.plane].add(e.line)
    for e in events:
        if not trace.is_device_plane(e.plane):
            continue
        line = (trace.MODULE_LINE if trace.MODULE_LINE in lines[e.plane]
                else trace.OPS_LINE)
        c = (trace._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
             if e.line == line else None)
        if c is not None:
            busy[e.plane].append(c)
    gaps: List[Tuple[float, float]] = []
    for plane in lines:
        edge = lo
        for a, b in trace._union(busy[plane]) + [(hi, hi)]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
    return gaps


def summarize(events: List[trace.Event]) -> Dict:
    """``trace.reduce_events`` with each idle gap split over the spans the
    host was in during it, and the spans' times."""
    out = trace.reduce_events(events)
    _, lo, hi, spans = _thread_spans(events)
    pieces = _innermost(spans, lo, hi)
    labeled: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in sorted(_idle_gaps(events, lo, hi)):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            if name.startswith(trace.SPAN_PREFIX):
                name = name[len(trace.SPAN_PREFIX):]
            labeled[name] += (min(b, pb) - max(a, pa)) * 1e-9
            k += 1
    n_dev = max(out["devices"], 1)
    out["idle_gaps"] = [[k, v / n_dev] for k, v in sorted(
        labeled.items(), key=lambda kv: -kv[1])[:trace.TOP]]
    out["spans"] = span_times(events)
    return out
