"""Serve a small model with batched requests over HHZS-tiered paged KV.

Deliberately undersizes the HBM pool so the tier manager must demote /
promote / prefix-cache sequences mid-flight — the serving-side analogue of
the paper's placement, migration, and caching (DESIGN.md §Adaptation).

  PYTHONPATH=src python examples/serve_paged.py
"""
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import init_params
from repro.serving import Request, ServingEngine


def main():
    enable_compile_cache()
    cfg = get_config("qwen3-1.7b").smoke()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, hbm_zones=6, host_zones=64,
                        pages_per_zone=2, page_size=8, max_batch=4,
                        cache_zones=2)
    rng = np.random.default_rng(7)
    n_req = 12
    for i in range(n_req):
        plen = int(rng.integers(8, 24))
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               plen).astype(np.int32),
                           max_new_tokens=int(rng.integers(4, 10))))
    t0 = time.time()
    stats = eng.run(max_steps=200)
    wall = time.time() - t0
    print(f"served {stats['done']}/{n_req} requests, "
          f"{stats['tokens_out']} tokens in {stats['steps']} engine steps "
          f"({stats['tokens_out']/wall:.1f} tok/s wall)")
    print(f"KV placement: hbm={stats['hbm_placements']} "
          f"host={stats['host_placements']}")
    print(f"tiering: demotions={stats['demotions']} "
          f"promotions={stats['promotions']} "
          f"migrated={stats['bytes_migrated']/1e6:.2f}MB")
    print(f"prefix cache: admits={stats['cache_admits']} "
          f"hits={stats['cache_hits']}")
    assert stats["done"] == n_req


if __name__ == "__main__":
    main()
