"""Open-loop chat traffic through ``repro.serving.ServingEngine.step`` on a
dense model over HHZS-tiered paged KV.

Set-up makes the weights from the seed on the device, builds the engine
with the configuration's pools, and warms every program the mix can use:
the layer program at each new-token count and resident length the mix
can reach (both residual dtypes), the HBM page gather at each resident
length, each zone copy between tiers, then one engine pass over a request
per prompt length for the engine's eager steps.

The window submits each request when it is due on the wall clock
(``chipbench.gen.chat_requests``) before each ``step()``; a token's time
is the end of the step that produced it.  The mix's rate is above the
engine's knee, so the queue stays full and the window ends at its close
with requests still queued: tokens per second is the engine's capacity,
and the gaps between tokens are a per-layer tail.

For the check, the harness reads the KV of each request that finishes in
the window through the engine's own ``_gather_kv`` just before
``release``.  Once the window has closed and the engine is freed, the
float32 reference runs once over a seeded sample of the finished requests
(the longest among them), each prompt with its served tokens, and the run
compares (a) how far below the reference's best logit each served token
lies, and (b) each layer's K and V.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import replace
from typing import Dict, List

import numpy as np

from chipbench import gen, harness
from chipbench.reference import dense_lm
from chipbench.roofline import dense_layer

WARMUP_RID = 10 ** 9
# threads that compile the warm-up's programs while the next is lowered
COMPILE_THREADS = 4


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for this configuration; raises where
    a published width differs from ``hf_config``."""
    from repro.configs import get_config
    hf = conf["hf_config"]
    cfg = replace(get_config(conf["registry_name"]), **conf["run_overrides"])
    want = {"num_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
            "num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "head_dim_": hf["head_dim"], "d_ff": hf["intermediate_size"],
            "vocab_size": hf["vocab_size"], "rope_theta": hf["rope_theta"],
            "norm_eps": hf["rms_norm_eps"],
            "tie_embeddings": hf["tie_word_embeddings"],
            "qkv_bias": hf["attention_bias"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"registry {conf['registry_name']!r} with "
                         f"{conf['run_overrides']} is not the published "
                         f"model: {got} != {want}")
    return cfg


def roofline_dims(conf: Dict) -> Dict:
    hf, eng = conf["hf_config"], conf["engine"]
    nbytes = {"bfloat16": 2, "float32": 4}
    return {"hidden": hf["hidden_size"], "heads": hf["num_attention_heads"],
            "kv_heads": hf["num_key_value_heads"], "head_dim": hf["head_dim"],
            "ffn": hf["intermediate_size"],
            "weight_bytes": nbytes[conf["weights_dtype"]],
            "kv_bytes": nbytes[eng["kv_dtype"]], "act_bytes": 4}


class State:
    pass


def _watch(st) -> None:
    """Host spans and records around the engine's own calls."""
    eng = st.eng
    forward, gather, tick = eng._forward_tokens, eng._gather_kv, eng.mgr.tick
    release = eng.mgr.release

    def forward_tokens(req, tokens):
        s = eng.mgr.seqs[req.rid].length
        t0 = time.perf_counter()
        with harness.span("forward"):
            out = forward(req, tokens)
        st.forwards.append((t0, len(tokens), s))
        return out

    def gather_kv(req):
        with harness.span("gather_kv"):
            return gather(req)

    def tier_tick(active):
        with harness.span("tier_tick"):
            return tick(active)

    def release_seq(sid):
        if st.capture and sid < WARMUP_RID:
            st.kv[sid] = gather(_Rid(sid))
        return release(sid)

    eng._forward_tokens, eng._gather_kv = forward_tokens, gather_kv
    eng.mgr.tick, eng.mgr.release = tier_tick, release_seq


class _Rid:
    def __init__(self, rid):
        self.rid = rid


def _warm_programs(st) -> int:
    """Compile (or load from the cache) the layer program at every (new
    tokens, resident length, residual dtype) and the HBM page gather at
    every resident length the mix can reach; returns how many programs.
    Each is lowered here, one after another, and compiled on a few
    threads: the compiler runs outside the interpreter lock, the lowering
    inside it."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine as E
    cfg, eng, t = st.cfg, st.eng, st.traffic
    prompts = gen.warmup_lengths(t)
    # the longest a sequence gets: its last token is served, not stored
    max_length = max(prompts) + t["output"]["max"] - 1
    kv, hd, L = cfg.num_kv_heads, cfg.head_dim_, cfg.num_layers
    shape = jax.ShapeDtypeStruct
    # resident lengths a decode sees, and a gather (the check's too, at
    # release)
    decoded = range(min(prompts), max_length)
    gathered = range(min(prompts), max_length + 1)
    li = shape((), jnp.int32)

    def layer(tokens, resident, dtype):
        pk = shape((L, resident, kv, hd), jnp.float32)
        x = shape((1, tokens, cfg.d_model), dtype)
        return E._layer_forward.lower(cfg, st.params["layers"], li, x,
                                      shape((1, tokens), jnp.int32), pk, pk)

    def gather(n):
        pages = shape((-(-n // eng.page_size),), jnp.int32)
        return E._take_pages_device.lower(eng.hbm.k, pages, n)

    calls = [(layer, (tokens, resident, dtype))
             for tokens, resident in [(p, 0) for p in prompts]
             + [(1, s) for s in decoded]
             for dtype in (jnp.bfloat16, jnp.float32)]
    calls += [(gather, (n,)) for n in gathered]
    with ThreadPoolExecutor(COMPILE_THREADS) as pool:
        done = [pool.submit(lower(*args).compile) for lower, args in calls]
        for f in done:
            f.result()
    # the engine makes each page index from a list: one conversion
    # program per page count
    page_counts = {-(-n // eng.page_size) for n in gathered}
    for n in page_counts:
        jax.block_until_ready(jnp.asarray(list(range(n)), jnp.int32))
    return len(calls) + len(page_counts)


def _warm_tier_copies(eng) -> None:
    """Run each zone copy the tier manager makes (host to HBM on a
    promotion, HBM to host on a demotion, HBM to HBM on a prefix-cache
    admit) once, on scratch zones, so that none compiles in the window."""
    import jax
    hbm, host = eng.hbm, eng.host
    token = np.zeros(hbm.k.shape[:1] + hbm.k.shape[3:], np.float32)
    zones = {"h0": host.alloc_zone(-2), "h1": host.alloc_zone(-2),
             "d0": hbm.alloc_zone(-2), "d1": hbm.alloc_zone(-2)}
    host.write_token(zones["h0"], token, token)
    hbm.write_token(zones["d0"], token, token)
    hbm.copy_zone_from(host, zones["h0"], zones["d1"])
    host.copy_zone_from(hbm, zones["d0"], zones["h1"])
    hbm.copy_zone_from(hbm, zones["d0"], zones["d1"])
    jax.block_until_ready((hbm.k, hbm.v))
    for name, z in zones.items():
        (host if name[0] == "h" else hbm).reset_zone(z)


def _warm_engine(st) -> None:
    from repro.serving import Request
    t, eng = st.traffic, st.eng
    rng = gen.rng_for(st.seed, 5)
    for i, p in enumerate(gen.warmup_lengths(t)):
        eng.submit(Request(rid=WARMUP_RID + i,
                           max_new_tokens=t["warmup_new_tokens"],
                           prompt=rng.integers(0, st.cfg.vocab_size, p,
                                               dtype=np.int32)))
    while eng.queue or eng.running:
        eng.step()
    st.warm_stats = dict(eng.mgr.stats)


def setup(conf: Dict, traffic: Dict, seed: int):
    import jax
    from chipbench import dense_weights
    from repro.serving import ServingEngine
    st = State()
    st.conf, st.traffic, st.seed = conf, traffic, seed
    st.cfg = model_config(conf)
    st.dims = dense_lm.dims_of(conf["hf_config"])
    key = jax.random.PRNGKey(int(gen.rng_for(seed, 6).integers(2 ** 31)))
    with harness.span("weights"):
        st.params = jax.block_until_ready(dense_weights.make(key, st.dims))
    e = conf["engine"]
    st.eng = ServingEngine(st.cfg, st.params, hbm_zones=e["hbm_zones"],
                           host_zones=e["host_zones"],
                           pages_per_zone=e["pages_per_zone"],
                           page_size=e["page_size"], max_batch=e["max_batch"],
                           cache_zones=e["cache_zones"])
    st.forwards, st.kv, st.capture = [], {}, False
    _watch(st)
    with harness.span("warm_programs"):
        st.programs_warmed = _warm_programs(st)
    with harness.span("warm_engine"):
        _warm_tier_copies(st.eng)
        _warm_engine(st)
    return st


def window(st, seconds: float, tracer: harness.Tracer) -> None:
    from repro.serving import Request
    eng, t = st.eng, st.traffic
    reqs = gen.chat_requests(t, seconds, st.seed, st.cfg.vocab_size)
    st.reqs, st.kv, st.capture = reqs, {}, True
    st.forwards.clear()
    live: Dict[int, object] = {}
    times: Dict[int, List[float]] = {r["rid"]: [] for r in reqs}
    lateness, steps = [], []
    lead = max(0.0, (seconds - t["trace_seconds"]) / 2)
    # the wall span the profiler holds the host, its start and stop included
    profiled = []
    before = dict(eng.mgr.stats)
    nxt = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if tracer.enabled:
            if tracer.t0 is None and now >= lead:
                profiled.append(now)
                tracer.start()
            elif tracer.t1 is None and now >= lead + t["trace_seconds"]:
                tracer.stop()
                profiled.append(time.perf_counter() - t0)
        while nxt < len(reqs) and reqs[nxt]["due"] <= now:
            r = reqs[nxt]
            live[r["rid"]] = req = Request(rid=r["rid"], prompt=r["prompt"],
                                           max_new_tokens=r["max_new"])
            eng.submit(req)
            lateness.append(now - r["due"])
            nxt += 1
        if not live:
            with harness.span("wait_arrival"):
                time.sleep(max(0.0, min(reqs[nxt]["due"] if nxt < len(reqs)
                                        else seconds, seconds) - now))
            continue
        had = {rid: len(q.out_tokens) for rid, q in live.items()}
        s0 = time.perf_counter()
        with harness.span("step"):
            eng.step()
        s1 = time.perf_counter()
        steps.append((s0 - t0, s1 - t0))
        for rid, q in list(live.items()):
            times[rid].extend([s1 - t0] * (len(q.out_tokens) - had[rid]))
            if q.state == "done":
                del live[rid]
    tracer.stop()
    if len(profiled) == 1:
        profiled.append(time.perf_counter() - t0)
    st.profiled = profiled or None
    st.capture = False
    st.pool_off_dtype = pool_off_dtype(eng, st.conf["engine"]["kv_dtype"])
    st.at_close = dict(eng.mgr.stats)
    st.before, st.times, st.steps = before, times, steps
    st.lateness, st.seconds, st.submitted = lateness, seconds, nxt
    st.done = {q.rid: q for q in eng.done
               if q.rid < WARMUP_RID and q.rid in st.kv}
    st.in_flight = len(live)
    st.trace_span = (tracer.t0 - t0 if tracer.t0 else None,
                     tracer.t1 - t0 if tracer.t1 else None)
    st.t0 = t0


def _end_to_end(st) -> Dict:
    tokens = sum(x <= st.seconds for ts in st.times.values() for x in ts)
    return {"serve_tokens_per_s": tokens / st.seconds}


def _itl_p95_ms(st):
    """95th percentile of the gaps between each request's consecutive
    tokens done by the close, leaving out gaps that overlap the span the
    profiler held; None without a gap."""
    skip = st.profiled
    gaps = []
    for ts in st.times.values():
        done = [x for x in ts if x <= st.seconds]
        gaps += [b - a for a, b in zip(done, done[1:])
                 if skip is None or b <= skip[0] or a >= skip[1]]
    return 1e3 * harness.percentile(gaps, 95) if gaps else None


def _ttft(st) -> List[float]:
    """Time to first token of the requests that had one by the close."""
    return [st.times[r["rid"]][0] - r["due"] for r in st.reqs
            if st.times[r["rid"]] and st.times[r["rid"]][0] <= st.seconds]


def _layer_context(st) -> Dict:
    T, L = st.seconds, st.cfg.num_layers
    dims = roofline_dims(st.conf)
    fwd = [(t - st.t0, n, s) for t, n, s in st.forwards]
    flops = sum(L * dense_layer.work(n, s, dims)["flops"]
                + dense_layer.head_flops(dims, st.cfg.vocab_size)
                for t, n, s in fwd if t <= T)
    step_s = sum(b - a for a, b in st.steps if b <= T)
    lo, hi = st.trace_span
    traced = [(n, s) for t, n, s in fwd
              if lo is not None and lo <= t <= hi]
    tokens = sum(1 for r in st.reqs for x in st.times[r["rid"]] if x <= T)
    return {
        "counters": {
            "bytes_migrated": st.at_close["bytes_migrated"]
            - st.before["bytes_migrated"],
            "tokens_out": tokens},
        "model_flops": flops, "step_s": step_s,
        "itl_p95_ms": _itl_p95_ms(st),
        "traced_layer_calls": [(n, s, L) for n, s in traced],
        "dims": dims,
    }


def reference_inputs(st):
    """Free the engine; the sampled requests' prompts with their served
    tokens, padded to the mix's longest sequence, as the reference takes
    them: (tokens, logit positions, served, valid, rids)."""
    import gc
    del st.eng
    gc.collect()
    finished = [r for r in st.reqs if r["rid"] in st.done]
    rids = gen.sample_finished(finished, int(st.traffic["check_requests"]),
                               st.seed) if finished else []
    o = st.traffic["output"]["max"]
    s_ref = st.traffic["prompt"]["max"] + o - 1
    reqs = {r["rid"]: r for r in st.reqs}
    toks = np.zeros((len(rids), s_ref), np.int32)
    sel = np.zeros((len(rids), o), np.int32)
    served = np.zeros((len(rids), o), np.int32)
    valid = np.zeros((len(rids), o), bool)
    for b, rid in enumerate(rids):
        p, out = reqs[rid]["prompt"], st.done[rid].out_tokens
        seq = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        toks[b, :len(seq)] = seq
        sel[b, :len(out)] = len(p) - 1 + np.arange(len(out))
        served[b, :len(out)] = out
        valid[b, :len(out)] = True
    return toks, sel, served, valid, rids


def reference_numbers(params, toks, sel, served, valid, dims, kv, rids,
                      quant=None, kv_dtype=None) -> Dict:
    """Worst served-token logit gap and worst per-layer relative K/V error
    of the engine against the float32 reference (``quant`` or
    ``kv_dtype``: the control, weights or KV held below the configuration,
    whose first-ranked token stands in for the served one)."""
    import jax.numpy as jnp
    logits, ks, vs = dense_lm.forward(params, jnp.asarray(toks),
                                      jnp.asarray(sel), dims)
    control = quant is not None or kv_dtype is not None
    if control:
        c_logits, c_ks, c_vs = dense_lm.forward(params, jnp.asarray(toks),
                                                jnp.asarray(sel), dims, quant,
                                                kv_dtype)
        served = np.asarray(jnp.argmax(c_logits, -1))
        del c_logits
    best = np.asarray(jnp.max(logits, -1))
    at = np.asarray(jnp.take_along_axis(logits, jnp.asarray(served)[..., None],
                                        -1)[..., 0])
    gap = float(np.max(np.where(valid, best - at, 0.0))) if valid.any() \
        else float("nan")
    errs = []
    for b, rid in enumerate(rids):
        n = int(valid[b].sum()) + int(sel[b, 0])       # tokens with KV
        for name, ref in (("k", ks), ("v", vs)):
            r = ref[:, b, :n]
            if control:
                e = (c_ks if name == "k" else c_vs)[:, b, :n]
            else:
                e = kv[rid][0 if name == "k" else 1]
            num = jnp.sqrt(jnp.sum((e.astype(jnp.float32) - r) ** 2,
                                   axis=(1, 2, 3)))
            den = jnp.sqrt(jnp.sum(r ** 2, axis=(1, 2, 3)))
            errs.append(float(jnp.max(num / den)))
    return {"logit_gap": gap,
            "kv_rel_err": max(errs) if errs else float("nan")}


def pool_off_dtype(eng, kv_dtype: str) -> int:
    """How many of the engine's KV arrays (K and V of each tier) are not
    held in the configuration's ``kv_dtype``: a pool in a lower type than
    stated is another result, which the numbers against the reference
    barely see, as bfloat16 rounding is smaller than the K/V error the
    engine's own bf16 products make."""
    return sum(str(a.dtype) != kv_dtype
               for pool in (eng.hbm, eng.host) for a in (pool.k, pool.v))


def checks_of(numbers: Dict, limits: Dict) -> List[Dict]:
    """Each number ``correct`` compares, beside its limit: ``numbers``
    holds ``reference_numbers``' two, ``kv_pool_off_dtype`` and the count
    of ``requests`` checked."""
    return [
        {"name": "served_logit_gap", "value": numbers["logit_gap"],
         "limit": limits["served_logit_gap"]},
        {"name": "kv_rel_err", "value": numbers["kv_rel_err"],
         "limit": limits["kv_rel_err"]},
        {"name": "kv_pool_off_dtype", "value": numbers["kv_pool_off_dtype"],
         "limit": 0},
        {"name": "no_finished_request", "value": int(not numbers["requests"]),
         "limit": 0},
    ]


def finish(st) -> Dict:
    e2e = _end_to_end(st)
    layer = _layer_context(st)
    ms, ttft = st.at_close, _ttft(st)
    info = {
        "requests_submitted": st.submitted, "requests_finished": len(st.done),
        "requests_in_flight_at_close": st.in_flight,
        "itl_p95_ms": layer["itl_p95_ms"],
        "ttft_p50_s": harness.percentile(ttft, 50) if ttft else None,
        "ttft_p95_s": harness.percentile(ttft, 95) if ttft else None,
        "generator_late_p95_s": harness.percentile(st.lateness, 95)
        if st.lateness else 0.0,
        "generator_late_max_s": max(st.lateness, default=0.0),
        "demotions": ms["demotions"] - st.before["demotions"],
        "promotions": ms["promotions"] - st.before["promotions"],
        "warmup_demotions": st.warm_stats["demotions"],
        "warmup_promotions": st.warm_stats["promotions"],
        "steps": len(st.steps), "forwards": len(st.forwards),
        "programs_warmed": st.programs_warmed,
    }
    toks, sel, served, valid, rids = reference_inputs(st)
    ref = reference_numbers(st.params, toks, sel, served, valid, st.dims,
                            st.kv, rids)
    checks = checks_of(dict(ref, kv_pool_off_dtype=st.pool_off_dtype,
                            requests=len(rids)), st.traffic["limits"])
    info.update(checked_tokens=int(valid.sum()), checked_requests=len(rids))
    return {"end_to_end": e2e, "layer": layer, "checks": checks,
            "attempted": st.submitted, "failed": 0, "info": info}
