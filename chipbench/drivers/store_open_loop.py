"""Open-loop YCSB traffic through the hinted LSM store's published cell
entry, ``repro.workloads.runner.run_open_loop``, with the read path's
batched Bloom probe on the device.

Set-up loads the configuration's objects in the configuration's fixed
order, flushes, and warms every compiled probe shape and the store with a
short run of the same traffic.  The window is one ``run_open_loop`` call,
as a published cell makes it, over ``seconds * virtual_s_per_wall_s``
virtual seconds (the span that fills ``seconds`` of wall time on a v5e
host).  Every seed runs the same data, the same op stream and the same
number of arrivals; the seed orders the gaps between arrivals
(``gen.StratifiedPoisson``).  So each seed is the same amount of work, and
``store_ops_per_s`` (simulated operations completed over the call's wall
time) moves with speed, not with the draw.

The harness watches the program from outside: it wraps
``filters.probe_pairs_device`` (a host span per call, through
``block_until_ready``, keeping each call's inputs and hit mask) and the
tree's ``get_batch`` (keeping every answer).  Once the window has closed,
every kept hit mask is compared with the plain probe, every answer with
the set of loaded keys, and a sample of the touched keys and of keys
never written is read back through ``DB.get_batch``.
"""
from __future__ import annotations

import time
from dataclasses import replace
from typing import Dict, List

import numpy as np

from chipbench import gen, harness
from chipbench.reference import kv_set


def scenario(conf: Dict):
    """The configuration's ``ScenarioConfig``, field by field."""
    from repro.lsm import ScenarioConfig
    from repro.lsm.tree import LSMConfig
    from repro.zoned.device import DeviceTiming
    sc = dict(conf["scenario"])
    lsm = dict(sc.pop("lsm"))
    lsm["level_targets"] = tuple(lsm["level_targets"])
    return ScenarioConfig(ssd_timing=DeviceTiming(**sc.pop("ssd_timing")),
                          hdd_timing=DeviceTiming(**sc.pop("hdd_timing")),
                          lsm=LSMConfig(**lsm), **sc)


class State:
    def __init__(self, conf, traffic, seed, db):
        self.conf, self.traffic, self.seed, self.db = conf, traffic, seed, db
        self.n = int(conf["objects"])
        self.recording = False
        self.calls: List[tuple] = []       # (t0, seconds, pairs, k, inputs, out)
        self.answers: List[tuple] = []     # (keys, found)
        self.hook = None                   # called before each probe


def _watch(st: State) -> None:
    from repro.lsm import filters
    real_probe = filters.probe_pairs_device

    def probe(lo, hi, word_off, num_words, bits, k):
        if st.hook is not None:
            st.hook()
        t0 = time.perf_counter()
        with harness.span("probe_call"):
            out = real_probe(lo, hi, word_off, num_words, bits, k)
            out.block_until_ready()
        if st.recording:
            st.calls.append((t0, time.perf_counter() - t0, len(lo), int(k),
                             (lo, hi, word_off, num_words, bits), out))
        return out

    filters.probe_pairs_device = probe
    tree = st.db.tree
    real_get_batch = tree.get_batch

    def get_batch(keys):
        res = yield from real_get_batch(keys)
        if st.recording:
            st.answers.append((list(keys), [bool(f) for f, _ in res]))
        return res

    tree.get_batch = get_batch


def _open_loop(st: State, virtual_s: float, arrival_seed: int):
    """One ``run_open_loop`` call: the mix's fixed op stream (keys and op
    order from ``stream_seed``), with arrival times from ``arrival_seed``."""
    from repro.workloads.runner import run_open_loop
    from repro.workloads.ycsb import YCSB
    t = st.traffic
    spec = replace(YCSB[t["ycsb"]], alpha=float(t["zipf_alpha"]))
    arrivals = gen.StratifiedPoisson(t["rate_ops_per_virtual_s"], arrival_seed)
    return run_open_loop(st.db, spec, arrivals, virtual_s, n_keys=st.n,
                         read_batch=int(t["read_batch"]),
                         max_concurrency=int(t["max_concurrency"]),
                         seed=int(t["stream_seed"]))


def setup(conf: Dict, traffic: Dict, seed: int) -> State:
    from repro.lsm import DB
    from repro.workloads.ycsb import run_load
    db = DB(conf["scheme"], scenario(conf))
    st = State(conf, traffic, seed, db)
    with harness.span("load"):
        run_load(db, n_keys=st.n, seed=int(conf["load_seed"]))
        db.flush_all()
    _watch(st)
    with harness.span("warmup"):
        _warm_probe_shapes(st)
        _open_loop(st, traffic["warmup_wall_s"] * traffic["virtual_s_per_wall_s"],
                   int(traffic["warmup_seed"]))
    return st


def _warm_probe_shapes(st: State) -> None:
    """Compile (or load) the device probe at every padded shape the cell
    can reach: pair buckets up to a full read batch against the most L0
    files the tree allows, word buckets up to the whole store's filters.
    Background compactions move level images across buckets inside the
    window, so the shapes of a short warm-up run are not enough."""
    import jax
    from repro.kernels.bloom_probe.ops import probe_pairs
    from repro.lsm import filters
    lsm = st.conf["scenario"]["lsm"]
    words, k = filters.filter_params(st.n, lsm["filter_bits_per_key"])
    top_pairs = filters.bucket(int(st.traffic["read_batch"])
                               * lsm["l0_stall_files"],
                               filters.MIN_PAIRS_BUCKET)
    top_words = filters.bucket(words, filters.MIN_WORDS_BUCKET)
    pairs = filters.MIN_PAIRS_BUCKET
    while pairs <= top_pairs:
        z = np.zeros(pairs, np.uint32)
        nw = np.ones(pairs, np.uint32)
        off = np.zeros(pairs, np.int32)
        w = filters.MIN_WORDS_BUCKET
        while w <= top_words:
            jax.block_until_ready(probe_pairs(z, z, off, nw,
                                              np.zeros(w, np.uint32),
                                              k_hashes=k))
            w *= 2
        pairs *= 2


def window(st: State, seconds: float, tracer: harness.Tracer) -> None:
    tree = st.db.tree
    st.before = dict(tree.stats)
    # the traced part is the middle ``trace_seconds`` of the window, found
    # on the virtual clock so that it exists however fast the host runs
    virtual = seconds * st.traffic["virtual_s_per_wall_s"]
    share = min(1.0, st.traffic["trace_seconds"] / seconds)
    v0 = st.db.sim.now
    start, stop = (v0 + virtual * (1 - share) / 2,
                   v0 + virtual * (1 + share) / 2)

    def hook():
        now = st.db.sim.now
        if tracer.t0 is None and now >= start:
            tracer.start()
        elif tracer.t1 is None and now >= stop:
            tracer.stop()

    st.hook = hook if tracer.enabled else None
    st.recording = True
    t0 = time.perf_counter()
    with harness.span("open_loop"):
        st.result = _open_loop(st, virtual, st.seed)
    st.window_s = time.perf_counter() - t0
    st.recording = False
    st.hook = None
    tracer.stop()
    st.after = dict(tree.stats)
    st.trace_span = (tracer.t0, tracer.t1)


def finish(st: State) -> Dict:
    res = st.result
    model = kv_set.LoadedKeys(st.n)
    window_calls = list(st.calls)
    keys = np.concatenate([np.asarray(k, np.int64) for k, _ in st.answers]) \
        if st.answers else np.zeros(0, np.int64)
    found = np.concatenate([np.asarray(f, bool) for _, f in st.answers]) \
        if st.answers else np.zeros(0, bool)
    wrong = kv_set.wrong_answers(keys, found, model)

    # read back touched and never-written keys through the store's own
    # batched read, with the probe's hit masks kept again
    back = gen.readback_keys(keys, st.n, int(st.traffic["readback_keys"]),
                             st.seed)
    batch = int(st.traffic["read_batch"])
    st.calls, st.recording = [], True
    for i in range(0, len(back), batch):
        chunk = [int(k) for k in back[i:i + batch]]
        got = [bool(f) for f, _ in st.db.get_batch(chunk)]
        wrong += kv_set.wrong_answers(chunk, got, model)
    st.recording = False

    mismatched = 0
    for _, _, _, k, (lo, hi, off, nw, bits), out in window_calls + st.calls:
        want = kv_set.probe_pairs(lo, hi, off, nw, bits, k)
        got = np.asarray(out)[:len(lo)].astype(bool)
        mismatched += int(np.count_nonzero(got != want))

    b, a = st.before, st.after
    completed = int(res.n_measured)
    lo_t, hi_t = st.trace_span
    traced = [(c[2], c[3]) for c in window_calls
              if lo_t is not None and lo_t <= c[0] <= hi_t]
    return {
        "end_to_end": {"store_ops_per_s": completed / st.window_s},
        "attempted": int(res.n_arrived),
        "failed": int(res.n_arrived) - completed,
        "checks": [
            {"name": "probe_mismatched_pairs", "value": mismatched,
             "limit": 0},
            {"name": "wrong_answers", "value": int(wrong), "limit": 0},
            {"name": "window_without_device_probe",
             "value": int(not window_calls), "limit": 0},
        ],
        "layer": {
            "counters": {"filter_probes": a["filter_probes"] - b["filter_probes"],
                         "gets": a["gets"] - b["gets"]},
            "probe_call_s": [c[1] for c in window_calls],
            "traced_probe_calls": traced,
        },
        "info": {
            "window_s": st.window_s,
            "sim_ops_per_virtual_s": res.throughput,
            "sim_latency_p99_virtual_s": res.latency_p["p99"],
            "ops_completed": completed, "answers_checked": int(len(keys)),
            "readback_keys": int(len(back)),
            "probe_calls": len(window_calls),
            "flushes": a["flushes"] - b["flushes"],
            "compactions": a["compactions"] - b["compactions"],
        },
    }
