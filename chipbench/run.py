#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Sets the cell up (load, weights, warm-up of every shape its traffic uses),
measures for ``--seconds`` with nothing compiling inside, checks what the
timed path produced against the plain reference, and prints one JSON
result line last.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` a middle part of the window is
traced and the metrics are the cell's per-layer metrics.  Exits 2, with
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
_ROOT = _HERE.parent
# the harness's modules are imported as ``chipbench.*``, never by their
# bare names (``trace`` would shadow the standard library's)
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != _HERE]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, peaks=None,
             save_events=None) -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # the layer and probe programs compile in well under a second each;
    # keep them all, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = harness.device_info(cell.chips)
    if device is None:
        if require_chip:
            print(f"chipbench: {cell.name} needs {cell.chips} TPU chip(s); "
                  f"JAX found {jax.devices()}", file=sys.stderr)
            return 2
        d = jax.devices()
        device = {"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d)}
    peaks = peaks or peaks_for(device["kind"])
    compiles = harness.CompileCounter()
    state = cell.driver.setup(cell.config, cell.traffic, seed)
    setup_s = time.perf_counter() - T_START
    tracer = harness.Tracer(trace)
    before = compiles.count
    cell.driver.window(state, seconds, tracer)
    tracer.stop()
    in_window = compiles.count - before
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    summary = tracer.reduce(save_events)
    out = cell.driver.finish(state)
    harness.info(setup_s=setup_s, compiles_in_setup=before,
                 compile_s_in_setup=compiles.seconds,
                 compiles_in_window=in_window,
                 memory_peak_bytes=device["memory_peak_bytes"], **out["info"])

    metrics = {}
    if trace:
        ctx = dict(out["layer"], trace=summary, peaks=peaks)
        for m in cell.per_layer:
            reader = harness.load_module(
                harness.HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    else:
        e2e = dict(out["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result = {"correct": harness.all_within(out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace and summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    harness.emit(result, out["checks"])
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-events", default=None,
                    help="also write the trace's reduced events (JSON) here")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    return run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    save_events=args.save_events)


if __name__ == "__main__":
    sys.exit(main())
