#!/usr/bin/env python3
"""Smoke run of the store's read path and the tiered serving engine on one TPU.

    python chip_smoke.py [--seed N]

One process, one chip; all data comes from ``--seed``.  Phases, in order:

1. ``device_check``: the first JAX device must be a TPU, else it raises.
2. ``store_phase``: an HHZS store at the paper's 1/100 scale (2,097,152
   one-KiB objects) whose batched Bloom probe runs on the device.  A
   zipfian YCSB-C read mix (a share of the keys absent) goes through
   ``DB.get_batch``; then updates, inserts and deletes are read back,
   before and after a flush.  Every answer is checked against a set model
   of acknowledged writes, every device hit mask bit-for-bit against
   ``probe_pairs_np``.  Last, one open-loop YCSB-C run goes through
   ``run_open_loop(read_batch=64)``, the sweep's own entry point.
3. ``serving_phase``: ``ServingEngine`` on Qwen3-1.7B at published widths
   with bf16 parameters, over an HBM pool small enough to force demotions.
   Its tokens are compared with the dense decode reference
   (``models.model.decode_step``) fed the same tokens.

Each phase prints one JSON line of what it counted and timed; the last
line is ``{"ok": true, "device": {...}}``.  The phase functions take their
sizes as arguments, so the CPU tests run the same code at tiny sizes.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.lsm import DB, ScenarioConfig, filters  # noqa: E402
from repro.workloads.runner import PoissonArrivals, run_open_loop  # noqa: E402
from repro.workloads.ycsb import READ, YCSB, OpStream, run_load  # noqa: E402

# A served token may differ from the reference's argmax only where the
# reference itself barely separates them: the reference's logit for the
# served token is at most this far below its top logit.  Logits are bf16
# of magnitude 4-8 at these widths, where one bf16 ulp is 2**-5; eight ulps
# cover the engine's float32 KV pool against the reference's bf16 cache,
# while a token the reference does not rank near the top misses by units.
LOGIT_TOL = 8 * 2.0 ** -5

# distinct padded probe shapes a whole store phase may compile: the pair
# count and the filter image each fall in a few power-of-two buckets
MAX_PROBE_SHAPES = 16


def device_check(platform: str = "tpu") -> dict:
    """The JAX device, as JAX reports it; raises unless JAX sees exactly
    one device and it is a ``platform`` device (never falls back to
    another)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != platform or info["count"] != 1:
        raise RuntimeError(f"chip_smoke needs one {platform} device; JAX "
                           f"found {info}")
    return info


def peak_bytes_in_use():
    """The device allocator's peak so far (None where it keeps no stats)."""
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


class DeviceProbeCheck:
    """Checks every call of ``filters.probe_pairs_device`` while installed:
    the result must live on a ``platform`` device and its hit mask must
    equal ``probe_pairs_np`` on the same pairs, bit for bit."""

    def __init__(self, platform: str):
        self.platform = platform
        self.calls = 0
        self.pairs = 0

    def _checked(self, real):
        def probe(lo, hi, word_off, num_words, bits_concat, k_hashes):
            out = real(lo, hi, word_off, num_words, bits_concat, k_hashes)
            where = {d.platform for d in out.devices()}
            if where != {self.platform}:
                raise AssertionError(f"device probe ran on {where}, not "
                                     f"{self.platform}")
            got = np.asarray(out)[:len(lo)].astype(bool)
            want = filters.probe_pairs_np(lo, hi, word_off, num_words,
                                          bits_concat, k_hashes)
            if not np.array_equal(got, want):
                bad = int(np.count_nonzero(got != want))
                raise AssertionError(f"device hit mask differs from numpy "
                                     f"on {bad} of {len(lo)} pairs")
            self.calls += 1
            self.pairs += len(lo)
            return out
        return probe

    @contextlib.contextmanager
    def installed(self):
        real = filters.probe_pairs_device
        filters.probe_pairs_device = self._checked(real)
        try:
            yield self
        finally:
            filters.probe_pairs_device = real


def _check_reads(db, keys, live, batch: int) -> int:
    """Read ``keys`` through ``DB.get_batch`` in batches of ``batch`` and
    compare every answer with the set model; returns how many were hits."""
    hits = 0
    for i in range(0, len(keys), batch):
        chunk = keys[i:i + batch]
        for key, (found, _) in zip(chunk, db.get_batch(chunk)):
            if found != (key in live):
                raise AssertionError(f"get({key}) found={found}, model "
                                     f"says {key in live}")
            hits += found
    return hits


def store_phase(scenario: ScenarioConfig, n_keys: int, *, n_reads: int,
                batch: int = 256, absent_frac: float = 0.1,
                n_writes: int = 512, open_loop_rate: float = 2000.0,
                open_loop_s: float = 2.0, seed: int = 0,
                platform: str = "tpu") -> dict:
    """Load ``n_keys`` objects into an HHZS store probing on the device,
    then read, write and read back; every answer and every device hit mask
    is checked (see the module docstring)."""
    from repro.kernels.bloom_probe.ops import probe_pairs
    sc = replace(scenario, lsm=replace(scenario.lsm, filter_impl="jax"))
    db = DB("HHZS", sc)
    t0 = time.perf_counter()
    run_load(db, n_keys=n_keys, seed=seed)
    db.flush_all()
    load_s = time.perf_counter() - t0
    live = set(range(n_keys))
    rng = np.random.default_rng(seed + 1)
    check = DeviceProbeCheck(platform)
    compiled_before = probe_pairs._cache_size()
    with check.installed():
        t0 = time.perf_counter()
        stream = OpStream(db, YCSB["C"], n_reads, n_keys, seed=seed + 2)
        keys = [stream.resolve(READ, int(r)) for r in stream.ops.args]
        absent = n_keys + rng.choice(n_keys, n_reads, replace=False)
        keys = [int(a) if rng.random() < absent_frac else k
                for k, a in zip(keys, absent)]
        read_hits = _check_reads(db, keys, live, batch)
        read_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        picked = rng.choice(n_keys, 2 * n_writes, replace=False)
        updated, deleted = picked[:n_writes], picked[n_writes:]
        inserted = 2 * n_keys + rng.choice(n_keys, n_writes, replace=False)
        for key in np.concatenate([updated, inserted]):
            db.put(int(key))
            live.add(int(key))
        for key in deleted:
            db.delete(int(key))
            live.discard(int(key))
        touched = [int(k) for k in np.concatenate([updated, inserted,
                                                   deleted])]
        touched += keys[:n_writes]
        _check_reads(db, touched, live, batch)
        db.flush_all()
        _check_reads(db, touched, live, batch)
        write_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        res = run_open_loop(db, YCSB["C"], PoissonArrivals(open_loop_rate),
                            open_loop_s, n_keys=n_keys, read_batch=64,
                            seed=seed + 3)
        open_loop_wall_s = time.perf_counter() - t0
    if res.n_measured != res.n_arrived or res.op_counts["read"] != \
            res.n_arrived:
        raise AssertionError(f"open loop served {res.op_counts} of "
                             f"{res.n_arrived} arrivals")
    if check.calls == 0:
        raise AssertionError("no probe ran on the device")
    compiled = probe_pairs._cache_size() - compiled_before
    if compiled > MAX_PROBE_SHAPES:
        raise AssertionError(f"{compiled} probe shapes compiled")
    return {
        "phase": "store", "keys_loaded": n_keys, "load_s": load_s,
        "reads": n_reads, "read_hits": read_hits, "read_s": read_s,
        "writes": 3 * n_writes, "write_readback_s": write_s,
        "open_loop_ops": res.n_arrived,
        "open_loop_sim_throughput": res.throughput,
        "open_loop_wall_s": open_loop_wall_s,
        "device_probe_calls": check.calls, "device_probe_pairs": check.pairs,
        "probe_shapes_compiled": compiled,
        "peak_bytes_in_use": peak_bytes_in_use(),
    }


def _reference_logits(cfg, params, prompt, served, decode):
    """Dense-cache decode of ``prompt`` then ``served[:-1]``: row ``i`` is
    the reference's logits for the position where ``served[i]`` came out."""
    import jax.numpy as jnp
    from repro.models import model as M
    tokens = list(prompt) + list(served[:-1])
    caches = M.init_caches(cfg, 1, len(tokens))
    rows = []
    for pos, tok in enumerate(tokens):
        logits, caches = decode(params, jnp.asarray([[tok]], jnp.int32),
                                jnp.asarray([pos], jnp.int32), caches)
        if pos >= len(prompt) - 1:
            rows.append(np.asarray(logits[0, -1], np.float32))
    return rows


def serving_phase(cfg, *, n_requests: int = 4, prompt_len: int = 32,
                  new_tokens: int = 8, hbm_zones: int = 5,
                  host_zones: int = 32, pages_per_zone: int = 2,
                  page_size: int = 16, seed: int = 0,
                  tol: float = LOGIT_TOL) -> dict:
    """Serve ``n_requests`` through ``ServingEngine`` and hold its tokens to
    the dense decode reference (see ``LOGIT_TOL``)."""
    import jax
    from repro.models import init_params
    from repro.models import model as M
    from repro.serving import Request, ServingEngine
    t0 = time.perf_counter()
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    eng = ServingEngine(cfg, params, hbm_zones=hbm_zones,
                        host_zones=host_zones, pages_per_zone=pages_per_zone,
                        page_size=page_size, max_batch=n_requests,
                        cache_zones=1)
    rng = np.random.default_rng(seed)
    prompts = {}
    for rid in range(n_requests):
        prompts[rid] = rng.integers(0, cfg.vocab_size,
                                    prompt_len).astype(np.int32)
        eng.submit(Request(rid=rid, prompt=prompts[rid],
                           max_new_tokens=new_tokens))
    t0 = time.perf_counter()
    st = eng.run(max_steps=4 * (n_requests + new_tokens))
    engine_s = time.perf_counter() - t0
    if st["done"] != n_requests:
        raise AssertionError(f"engine finished {st['done']} of "
                             f"{n_requests} requests")
    if st["demotions"] < 1:
        raise AssertionError(f"HBM pool forced no demotion: {st}")

    t0 = time.perf_counter()
    decode = jax.jit(lambda p, tok, pos, c: M.decode_step(cfg, p, tok, pos, c),
                     donate_argnums=3)
    mismatches, rivals = [], []
    for req in sorted(eng.done, key=lambda r: r.rid):
        rows = _reference_logits(cfg, params, prompts[req.rid],
                                 req.out_tokens, decode)
        for i, (tok, logits) in enumerate(zip(req.out_tokens, rows)):
            top2 = np.sort(logits)[-2:]
            # tokens besides the top the check would let through here
            rivals.append(int(np.count_nonzero(
                logits >= logits.max() - tol)) - 1)
            if tok != int(np.argmax(logits)):
                mismatches.append({
                    "rid": req.rid, "pos": i, "served": tok,
                    "reference": int(np.argmax(logits)),
                    "gap": float(logits.max() - logits[tok]),
                    "top2_margin": float(top2[1] - top2[0])})
    reference_s = time.perf_counter() - t0
    for m in mismatches:
        print("token mismatch:", json.dumps(m), flush=True)
    worst = max((m["gap"] for m in mismatches), default=0.0)
    if worst > tol:
        raise AssertionError(f"served token {worst} below the reference's "
                             f"top logit (tolerance {tol})")
    return {
        "phase": "serving", "model": cfg.name, "requests": n_requests,
        "tokens_out": st["tokens_out"], "steps": st["steps"],
        "demotions": st["demotions"], "promotions": st["promotions"],
        "cache_admits": st["cache_admits"], "init_s": init_s,
        "engine_s": engine_s, "reference_s": reference_s,
        "token_mismatches": len(mismatches), "worst_gap": worst,
        "logit_tol": tol, "positions_with_rivals_in_tol":
        sum(r > 0 for r in rivals), "max_rivals_in_tol": max(rivals),
        "peak_bytes_in_use": peak_bytes_in_use(),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    device = device_check("tpu")
    print(json.dumps({"phase": "device", **device}), flush=True)

    def report(phase, *a, **kw):
        n0, t0 = len(compiles), time.perf_counter()
        out = phase(*a, **kw)
        out.update(phase_s=time.perf_counter() - t0,
                   backend_compiles=len(compiles) - n0,
                   compile_s=sum(compiles[n0:]))
        print(json.dumps(out), flush=True)

    from repro.configs import get_config
    sc = ScenarioConfig()
    report(store_phase, sc, sc.paper_keys, n_reads=8192, seed=args.seed)
    report(serving_phase, get_config("qwen3-1.7b"), seed=args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
