"""Wall-clock spans of the store's host code, in the JAX profiler's trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``hhzs:<name>``: the profiler keeps the spans in memory while it records
and writes them at ``stop_trace``, on the host plane of the xplane, on the
clock the device events use.  So a reduction of the trace can put each of
the device's idle gaps down to the innermost span the host was in.  When
no trace runs, ``span`` hands back one shared null context after a single
check of jaxlib's profiler state, so the spans on the store's per-put
path cost its load next to nothing; there is no other switch.

The DES processes are generators, so a span covers only a synchronous
section and never stays open across a ``yield``: a span left open while
its process is suspended would charge other processes' work to it and
break the nesting of the spans on the thread.

The virtual-time ``MetricsRegistry`` (``obs/metrics.py``) records the
simulated devices; these spans record how fast the code itself runs.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "hhzs:"

_NULL = contextlib.nullcontext()
_annotation = None
_recording = None     # ``TraceAnnotation.is_enabled`` once jax is loaded


def _resolve() -> bool:
    """Whether the profiler records, resolving ``TraceAnnotation`` once
    jax is loaded.  Only a process that imported jax can run the profiler,
    so the numpy route neither imports jax nor records anything; the
    answer is kept once jax is there."""
    global _annotation, _recording
    if "jax" not in sys.modules:
        return False
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:      # a jax that is loading or broken records nothing
        return False
    _annotation, _recording = TraceAnnotation, TraceAnnotation.is_enabled
    return _recording()


def span(name: str, **args):
    """A host span ``hhzs:<name>`` with the given arguments as its stats,
    or the shared null context while the profiler records nothing."""
    if _recording() if _recording is not None else _resolve():
        return _annotation(PREFIX + name, **args)
    return _NULL
