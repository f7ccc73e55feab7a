"""What every cell shares: finding a cell's parts by name, the device and
its memory, the compile counter, the traced sub-window and the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
and a traffic mix.  The configuration is the JSON file its entry names;
the mix is ``chipbench/traffic/<traffic>.json``, whose ``driver`` key
names the general generator and window driver that reads it
(``chipbench/drivers/<driver>.py``); each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  A new cell, mix or metric is new files
and entries, with no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(pending: bool = False) -> Dict:
    """``BENCHMARK.json``; with ``pending``, the entries of each file
    under ``chipbench/pending`` appended, as the PR that admits them
    would append them (for the rehearsal and the readings of limits)."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for path in sorted((HERE / "pending").glob("*.json")) if pending else ():
        part = json.loads(path.read_text())
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + part[key]
    return bench


class Cell:
    """One workload entry with its configuration, mix, driver and the
    metric entries it reports."""

    def __init__(self, bench: Dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        with open(ROOT / cfg_entry["file"]) as f:
            self.config = json.load(f)
        with open(HERE / "traffic" / f"{self.entry['traffic']}.json") as f:
            self.traffic = json.load(f)
        self.driver = load_module(
            HERE / "drivers" / f"{self.traffic['driver']}.py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [])
                          or ("workloads" not in m and m["moves"] in moved)]


def device_info(chips: int, platform: str = "tpu") -> Optional[Dict]:
    """The accelerator as JAX reports it, or None when JAX finds no
    ``platform`` device or fewer than ``chips`` of them."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError:
        return None
    if devs[0].platform != platform or len(devs) < chips:
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device so far."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Backend compiles, counted through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def span(name: str):
    """A host span the profiler records (``cb:<name>``); costs about a
    microsecond when no trace is running."""
    import jax
    return jax.profiler.TraceAnnotation("cb:" + name)


class Tracer:
    """The traced sub-window of a ``--trace 1`` run: a profiler trace from
    ``start()`` to ``stop()``, with the ``cb:window`` span inside it."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.t0 = self.t1 = None
        self._window = None

    def start(self) -> None:
        if not self.enabled or self.dir is not None:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)
        self._window = span("window")
        self._window.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        if self._window is None:
            return
        import jax
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()

    def reduce(self, save_events: Optional[str] = None) -> Optional[Dict]:
        if self.dir is None:
            return None
        from chipbench import trace
        try:
            events = trace.load_events(self.dir)
            if save_events:
                trace.save_events(events, save_events)
            return trace.reduce_events(events)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def checks_json(checks: List[Dict]) -> Dict:
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


def all_within(checks: List[Dict]) -> bool:
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)


def emit(result: Dict, checks: List[Dict]) -> None:
    """The checks as the last lines of stderr, then the result as the last
    line of stdout with ``checks`` as its last key."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = checks_json(checks)
    print(json.dumps(out), flush=True)


def info(**fields) -> None:
    """An earlier output line: what a run counted, not a metric."""
    print(json.dumps({"info": fields}, default=float), flush=True)
