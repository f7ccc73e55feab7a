#!/usr/bin/env python3
"""Run a store cell traced, with the program's own spans and counters read.

    python3 chipbench/trace_program.py --workload store-ycsb-c --seed <n> \
        --seconds <s> [--save-events <path>]

The run is ``run.py --trace 1`` as it is, with three additions made from
outside the harness: the trace is reduced by ``program_spans.summarize``
(gaps labeled by program spans too, and each span's self time); the
window's counters gain the differences of the tree's
``probe_calls`` and ``probe_h2d_bytes``, of ``Sim.scheduled``, and the
ops completed; and the metrics below are read beside the cell's own.  Its
``info`` line adds the point reads begun per wall second inside the traced
sub-window and in the rest of the window (less the profiler's start and
stop, which it also gives): what tracing costs the host.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != _HERE]
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

from chipbench import harness, program_spans, run, trace  # noqa: E402

LAYER_PROBE = "Device probe host side"
LAYER_BG = "HHZS middleware and background jobs"
LAYER_DES = "DES and request loop"
# entries as ``BENCHMARK.json`` would list them
METRICS = [
    {"name": "store_probe_h2d_bytes_per_call", "unit": "bytes",
     "better": "lower", "source": "program_counter", "layer": LAYER_PROBE},
    {"name": "store_probe_pad_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": LAYER_PROBE},
    {"name": "store_background_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": LAYER_BG},
    {"name": "store_des_self_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": LAYER_DES},
    {"name": "store_des_events_per_op", "unit": "events", "better": "lower",
     "source": "program_counter", "layer": LAYER_DES},
]
for _m in METRICS:
    _m.update(moves="store_ops_per_s", workloads=["store-ycsb-c"])


def instrument(cell: harness.Cell) -> None:
    """Adds the program's spans and counters to ``cell``'s traced run."""
    have = {m["name"] for m in cell.per_layer}
    cell.per_layer += [m for m in METRICS if m["name"] not in have
                       and cell.name in m["workloads"]]
    mod = cell.driver
    real_window, real_finish = mod.window, mod.finish

    def window(st, seconds, tracer):
        tree, sim = st.db.tree, st.db.sim
        marks = {}
        real_start, real_stop = tracer.start, tracer.stop

        def start():
            t = time.perf_counter()
            real_start()
            if "gets0" not in marks:
                marks.update(gets0=tree.stats["gets"],
                             profiler_s=tracer.t0 - t)

        def stop():
            first = tracer.t1 is None and tracer.t0 is not None
            if first:
                marks["gets1"] = tree.stats["gets"]
            real_stop()
            if first:
                marks["profiler_s"] += time.perf_counter() - tracer.t1

        def reduce(save_events=None):
            if tracer.dir is None:
                return None
            try:
                events = program_spans.load_events(tracer.dir)
                if save_events:
                    trace.save_events(events, save_events)
                return program_spans.summarize(events)
            finally:
                shutil.rmtree(tracer.dir, ignore_errors=True)

        tracer.start, tracer.stop, tracer.reduce = start, stop, reduce
        s0 = sim.scheduled
        real_window(st, seconds, tracer)
        st.scheduled = sim.scheduled - s0
        st.marks = marks

    def finish(st):
        out = real_finish(st)
        b, a = st.before, st.after
        ops = out["info"]["ops_completed"]
        out["layer"]["counters"].update(
            probe_calls=a["probe_calls"] - b["probe_calls"],
            probe_h2d_bytes=a["probe_h2d_bytes"] - b["probe_h2d_bytes"],
            scheduled=st.scheduled, ops_completed=ops)
        t0, t1 = st.trace_span
        if t0 is not None and "gets1" in st.marks:
            # the rest of the window, less the profiler's own start and
            # stop (stop writes the trace out)
            inside = st.marks["gets1"] - st.marks["gets0"]
            rest_s = st.window_s - (t1 - t0) - st.marks["profiler_s"]
            out["info"].update(
                profiler_start_stop_s=st.marks["profiler_s"],
                traced_reads_per_s=inside / (t1 - t0),
                untraced_reads_per_s=(a["gets"] - b["gets"] - inside)
                / rest_s)
        return out

    mod.window, mod.finish = window, finish


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save-events", default=None)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    instrument(cell)
    return run.run_cell(cell, args.seed, args.seconds, True,
                        save_events=args.save_events)


if __name__ == "__main__":
    sys.exit(main())
