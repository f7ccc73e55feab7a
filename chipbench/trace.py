"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load_events`` flattens the newest ``*.xplane.pb`` under a directory into
plain event tuples; ``reduce_events`` turns them into:

- ``window_s``: length of the harness's window span (``cb:window``);
- ``busy_s``: per device, the union of the intervals in which a program
  ran on it, clipped to the window, averaged over the devices;
- ``per_program``: device seconds per jitted program, by the name the
  program gave its function (``jit__layer_forward(12)`` -> ``_layer_forward``);
- ``device_ops``: the ten operations that took most device time (the
  first 100 characters of the HLO text the trace names them by);
- ``idle_gaps``: the device's idle time inside the window, summed by what
  the host was doing then: the innermost harness span (``cb:*``) that
  covers the middle of each gap.

Only the harness's own spans label gaps; spans inside the program are a
later change.  The reduction works on the tuples alone, so it is checked
on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "cb:window"
SPAN_PREFIX = "cb:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TOP = 10
OUTSIDE = "host outside harness spans"
OP_NAME_CHARS = 100


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_events(trace_dir: str) -> List[Event]:
    """Events of the newest xplane file under ``trace_dir``: device
    planes' module and op lines, and the harness's host spans."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: List[Event] = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OPS_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def save_events(events: Iterable[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def read_saved(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(*e) for e in json.load(f)]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def program_name(module: str) -> str:
    """``jit__layer_forward(123)`` -> ``_layer_forward``."""
    name = re.sub(r"\(\d+\)$", "", module)
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _label_gaps(gaps: List[Tuple[float, float]],
                spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of idle time by the innermost harness span covering each
    gap's middle; ``gaps`` are (middle, seconds), ``spans`` sorted by start."""
    out: Dict[str, float] = defaultdict(float)
    active: List[Tuple[float, float, str]] = []
    j = 0
    for mid, secs in sorted(gaps):
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] >= mid]
        inner = min(active, key=lambda s: s[1] - s[0], default=None)
        out[inner[2] if inner else OUTSIDE] += secs
    return out


def reduce_events(events: List[Event]) -> Dict:
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    w = windows[0]
    lo, hi = w.start_ns, w.start_ns + w.dur_ns
    spans = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name[len(SPAN_PREFIX):])
                   for e in events if e.name.startswith(SPAN_PREFIX)
                   and e.name != WINDOW_SPAN)

    by_plane: Dict[str, Dict[str, List[Event]]] = defaultdict(
        lambda: defaultdict(list))
    for e in events:
        if is_device_plane(e.plane):
            by_plane[e.plane][e.line].append(e)
    per_program: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    busy = []
    for plane, lines in sorted(by_plane.items()):
        busy_line = lines.get(MODULE_LINE) or lines.get(OPS_LINE) or []
        clipped = []
        for e in busy_line:
            c = _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
            if c is None:
                continue
            clipped.append(c)
            if e.line == MODULE_LINE:
                per_program[program_name(e.name)] += (c[1] - c[0]) * 1e-9
        for e in lines.get(OPS_LINE, []):
            c = _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
            if c is not None:
                ops[e.name[:OP_NAME_CHARS]] += (c[1] - c[0]) * 1e-9
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edge = lo
        for a, b in merged + [(hi, hi)]:
            if a > edge:
                gaps.append(((a + edge) / 2, (a - edge) * 1e-9))
            edge = max(edge, b)
    n_dev = len(busy)
    top = lambda d: [[k, v / max(n_dev, 1)] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "devices": n_dev,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev if n_dev else 0.0,
        "per_program": {k: v / max(n_dev, 1) for k, v in per_program.items()},
        "device_ops": top(ops),
        "idle_gaps": top(_label_gaps(gaps, spans)),
    }
