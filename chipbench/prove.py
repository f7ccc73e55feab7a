#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on one chip.

    python3 chipbench/prove.py serve --seeds 1,2,3 --seconds 20
    python3 chipbench/prove.py store --seeds 1,2,3 --seconds 10

``serve``: per seed, the cell is set up and driven for a short window at
its own load; then the numbers a run compares are read for the program
(served tokens and KV against the float32 reference, the pool's type)
and for the controls: the reference with its weights rounded to int8 and
to float8, and with its K/V held in bfloat16 below the configuration's
float32, whose first-ranked token and K/V stand in for the program's.
Each row says whether the run's own comparison passes it (``correct``).

``store``: per seed, the cell runs a short window with the control in the
program's place: a device probe that answers "absent" for one pair in
64 (a filter that rejects keys it holds), and the run's checks are read.

One JSON line per seed and side.  The benchmark's own runs never run a
control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != _HERE]
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

from chipbench import harness  # noqa: E402

# each control: its side's name, the weights' rounding, the KV's type
CONTROLS = (("control_int8", "int8", None), ("control_fp8", "fp8", None),
            ("control_bf16_kv", None, "bfloat16"))


def serve_readings(cell: harness.Cell, seed: int, seconds: float) -> list:
    drv = cell.driver
    st = drv.setup(cell.config, cell.traffic, seed)
    drv.window(st, seconds, harness.Tracer(False))
    toks, sel, served, valid, rids = drv.reference_inputs(st)
    stated = cell.config["engine"]["kv_dtype"]
    rows = []
    for side, q, kv in (("program", None, None),) + CONTROLS:
        # the engine's pools, or the control's K and V held in its type
        off = (st.pool_off_dtype if side == "program"
               else 2 * (kv not in (None, stated)))
        nums = dict(drv.reference_numbers(st.params, toks, sel, served, valid,
                                          st.dims, st.kv, rids, quant=q,
                                          kv_dtype=kv),
                    kv_pool_off_dtype=off, requests=len(rids))
        checks = drv.checks_of(nums, cell.traffic["limits"])
        rows.append(dict(side=side, seed=seed, tokens=int(valid.sum()),
                         correct=harness.all_within(checks), **nums))
    return rows


def drop_hits(every: int = 64):
    """Install the store's control: the device probe answers "absent" for
    every ``every``-th pair it is asked, whatever the filter holds."""
    import jax.numpy as jnp
    from repro.lsm import filters
    real = filters.probe_pairs_device

    def probe(lo, hi, word_off, num_words, bits, k):
        out = real(lo, hi, word_off, num_words, bits, k)
        keep = (jnp.arange(out.shape[0]) % every) != 0
        return jnp.where(keep, out, 0).astype(out.dtype)

    filters.probe_pairs_device = probe
    return real


def store_control(cell: harness.Cell, seed: int, seconds: float) -> dict:
    from repro.lsm import filters
    drv = cell.driver
    real = drop_hits()
    try:
        st = drv.setup(cell.config, cell.traffic, seed)
        drv.window(st, seconds, harness.Tracer(False))
        out = drv.finish(st)
    finally:
        filters.probe_pairs_device = real
    return dict(side="control_drop_hits", seed=seed,
                **{c["name"]: c["value"] for c in out["checks"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("store", "serve"))
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.load_benchmark(pending=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        if args.what == "serve":
            cell = harness.Cell(bench, "serve-chat-tiered")
            rows = serve_readings(cell, seed, args.seconds)
        else:
            cell = harness.Cell(bench, "store-ycsb-c")
            rows = [store_control(cell, seed, args.seconds)]
        for r in rows:
            print(json.dumps(r, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
