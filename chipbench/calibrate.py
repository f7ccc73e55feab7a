#!/usr/bin/env python3
"""How the fixed numbers in the traffic files were found, on one chip.

    python3 chipbench/calibrate.py store
    python3 chipbench/calibrate.py serve --rates 1.5,2.5,3.5 --seconds 30

``store`` runs the published sweep's rate probe for YCSB C at the
configuration's key count (``repro.workloads.sweep.calibrated_arrivals``:
a seeded closed-loop probe of the B3 baseline; the offered rate is 0.5x
its service rate), then sets the cell up at that rate and times open-loop
windows to find how many virtual seconds fill one wall second.

``serve`` sets the serving cell up once and runs one window per offered
rate, printing per rate the tokens per second and the backlog at the
window's close.  The knee is the highest rate whose backlog does not grow;
the cell's rate is 0.8x the knee.

Each prints one JSON line per measurement.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != _HERE]
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

from chipbench import harness  # noqa: E402


def store_rate(cell: harness.Cell) -> float:
    from repro.workloads.sweep import calibrated_arrivals
    n = cell.config["objects"]
    div = cell.config["published"]["objects"] // 100 // n
    arr = calibrated_arrivals(["poisson"], [cell.traffic["ycsb"]],
                              key_div=max(div, 1), load_div=1,
                              ssd_zones=cell.config["scenario"]["ssd_zones"])
    return arr[cell.traffic["ycsb"]][0].rate


def store_pace(cell: harness.Cell, seed: int, virtual_s: list) -> list:
    """Wall seconds of open-loop windows of these virtual lengths (0: as
    many as fill about 15 wall seconds at the previous window's pace)."""
    drv = cell.driver
    st = drv.setup(cell.config, cell.traffic, seed)
    out = []
    for i, v in enumerate(virtual_s):
        if v <= 0:      # size this window to ~15 wall s by the last pace
            v = round(15 * out[-1]["virtual_s_per_wall_s"], 1)
        t0 = time.perf_counter()
        res = drv._open_loop(st, v, seed + 100 + i)
        wall = time.perf_counter() - t0
        out.append({"virtual_s": v, "wall_s": wall,
                    "virtual_s_per_wall_s": v / wall,
                    "ops": int(res.n_measured),
                    "ops_per_wall_s": res.n_measured / wall,
                    "sim_ops_per_virtual_s": res.throughput})
        print(json.dumps(out[-1]), flush=True)
    return out


def serve_sweep(cell: harness.Cell, seed: int, rates: list,
                seconds: float) -> list:
    drv = cell.driver
    t0 = time.perf_counter()
    st = drv.setup(cell.config, cell.traffic, seed)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    out = []
    for i, rate in enumerate(rates):
        st.traffic = dict(cell.traffic, rate_per_s=rate)
        st.seed = seed + i
        drv.window(st, seconds, harness.Tracer(False))
        e2e = drv._end_to_end(st)
        ttft = drv._ttft(st)
        row = {"rate_per_s": rate, **e2e,
               "requests_submitted": st.submitted,
               "requests_finished": len(st.done),
               "requests_in_flight_at_close": st.in_flight,
               "requests_queued_at_close": st.submitted - len(st.done)
               - st.in_flight,
               "ttft_p50_s": harness.percentile(ttft, 50) if ttft else None,
               "forwards_per_s": len(st.forwards) / seconds,
               "demotions": st.at_close["demotions"] - st.before["demotions"]}
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("store", "serve"))
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="1.5,2.5,3.5,4.5")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--write", action="store_true",
                    help="store: write the rate and pace into the mix")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.load_benchmark(pending=True)
    if args.what == "store":
        cell = harness.Cell(bench, "store-ycsb-c")
        rate = store_rate(cell)
        print(json.dumps({"rate_ops_per_virtual_s": rate}), flush=True)
        cell.traffic["rate_ops_per_virtual_s"] = rate
        pace = store_pace(cell, args.seed, [2.0, 0.0])[-1]["virtual_s_per_wall_s"]
        if args.write:
            path = _HERE / "traffic" / f"{cell.entry['traffic']}.json"
            cell.traffic["virtual_s_per_wall_s"] = pace
            path.write_text(json.dumps(cell.traffic, indent=1) + "\n")
    else:
        cell = harness.Cell(bench, "serve-chat-tiered")
        serve_sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
                    args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
