"""Benchmark driver. One function per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run                 # everything
  PYTHONPATH=src python -m benchmarks.run --exp exp1,exp6 # subset
  PYTHONPATH=src python -m benchmarks.run --quick         # smaller loads

Storage rows (table1, fig2, exp1-exp6) reproduce the paper's experiments
on the scaled simulator (see benchmarks/storage_exps.py for methodology);
kernel rows time the jnp reference paths on CPU (the Pallas kernels target
TPU and are validated in interpret mode by the tests); roofline rows
summarise results/dryrun (produced by ``python -m repro.launch.dryrun``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def bench_kernels_reference() -> list:
    """Wall-time the pure-jnp oracle paths (CPU); labels are explicit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.selective_scan.ref import selective_scan_ref
    from repro.kernels.bloom_probe.ref import build_filter, bloom_probe_ref

    rows = []
    rng = np.random.default_rng(0)

    def timeit(fn, *args, n=5):
        fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
            else jax.block_until_ready(fn(*args))
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.time() - t0) / n * 1e6

    q = jnp.array(rng.standard_normal((1, 8, 1024, 64)), jnp.float32)
    k = jnp.array(rng.standard_normal((1, 2, 1024, 64)), jnp.float32)
    f = jax.jit(lambda q, k: attention_ref(q, k, k, causal=True))
    rows.append(f"kernel_attention_ref_cpu_b1h8s1024,{timeit(f, q, k):.0f},"
                f"jnp-oracle")
    dt = jnp.abs(jnp.array(rng.standard_normal((1, 256, 512)), jnp.float32))
    bx = jnp.array(rng.standard_normal((1, 256, 512, 16)) * .1, jnp.float32)
    c = jnp.array(rng.standard_normal((1, 256, 16)), jnp.float32)
    a = -jnp.abs(jnp.array(rng.standard_normal((512, 16)), jnp.float32))
    f2 = jax.jit(selective_scan_ref)
    rows.append(f"kernel_sscan_ref_cpu_t256d512,{timeit(f2, dt, bx, c, a):.0f},"
                f"jnp-oracle")
    member = jnp.array(rng.integers(0, 2**31, 4096), jnp.uint32)
    bits = build_filter(member, num_words=8192)
    f3 = jax.jit(bloom_probe_ref)
    rows.append(f"kernel_bloom_ref_cpu_n4096,{timeit(f3, member, bits):.0f},"
                f"jnp-oracle")
    return rows


def bench_roofline_summary() -> list:
    """CSV rows from the dry-run artifacts (one per compiled cell)."""
    rows = []
    d = Path("results/dryrun")
    if not d.exists():
        return ["roofline_missing,0,run python -m repro.launch.dryrun first"]
    for p in sorted(d.glob("*.json")):
        r = json.loads(p.read_text())
        tag = f"{r['arch']}__{r['shape']}__{r['mesh']}"
        if r["status"] != "ok":
            rows.append(f"roofline_{tag},0,{r['status']}")
            continue
        rl = r["roofline"]
        rows.append(
            f"roofline_{tag},{rl['bound_s'] * 1e6 if 'bound_s' in rl else max(rl['compute_s'], rl['memory_s'], rl['collective_s']) * 1e6:.0f},"
            f"dom={rl['dominant']};mfu={rl['mfu']:.3f};"
            f"comp={rl['compute_s']:.2e};mem={rl['memory_s']:.2e};"
            f"coll={rl['collective_s']:.2e}")
    return rows


def bench_serving() -> list:
    """Tokens/s of the tiered serving engine under HBM pressure (CPU)."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen3-1.7b").smoke()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, hbm_zones=6, host_zones=64,
                        pages_per_zone=2, page_size=8, max_batch=4,
                        cache_zones=1)
    rng = np.random.default_rng(0)
    for i in range(10):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               12).astype(np.int32),
                           max_new_tokens=6))
    t0 = time.time()
    st = eng.run(max_steps=120)
    wall = time.time() - t0
    return [f"serving_tiered_smoke,{wall / max(st['tokens_out'], 1) * 1e6:.0f},"
            f"tok={st['tokens_out']};demote={st['demotions']};"
            f"promote={st['promotions']};cache={st['cache_admits']}"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default="all",
                    help="comma list: table1,fig2,exp1..exp6,kernels,"
                         "roofline,serving")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    which = None if args.exp == "all" else args.exp.split(",")

    import benchmarks.storage_exps as SE
    if args.quick:
        SE.KEY_DIV = 4
        SE.SSD_SWEEP = [20, 60]

    rows = ["name,us_per_call,derived"]
    storage = [k for k in SE.ALL if which is None or k in which]
    if storage:
        rows += SE.run(storage)
    if which is None or {"kernels", "serving"} & set(which):
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    if which is None or "kernels" in which:
        rows += bench_kernels_reference()
    if which is None or "serving" in which:
        rows += bench_serving()
    if which is None or "roofline" in which:
        rows += bench_roofline_summary()
    print("\n".join(rows))


if __name__ == "__main__":
    main()
