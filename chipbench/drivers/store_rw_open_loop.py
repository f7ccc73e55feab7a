"""Open-loop YCSB traffic with writes through the hinted LSM store, every
write a versioned stamp, checked against the plain versioned map
(``chipbench/reference/kv_versions.py``).

The read side is ``store_open_loop``'s, by import: the configuration's
scenario, the watch on the device probe and on ``get_batch``, the warm-up
of every probe shape, and the window, one ``run_open_loop`` call of
``seconds * virtual_s_per_wall_s`` virtual seconds.  What this driver adds:

- set-up reads back a sample of the loaded keys and stops, before any
  warm-up, on a store that does not hand back their load stamps (a
  program whose writes carry no versions cannot run this cell);
- the warm-up runs the same mix until at least one flush and one
  compaction have completed and the window's first flush will start a
  compaction (``_warm_write_path``), so every window holds both: the
  window starts at this chosen phase of the write cycle, not at a random
  one, since it holds fewer updates than a whole flush cycle takes;
- the harness wraps ``tree.put`` and keeps each acknowledgement (key,
  stamp, order, virtual time), and ``tree.get_batch`` to keep when each
  read was issued and what it returned; one counter orders both;
- it times the tree's flush and compaction merges and its SST and filter
  builds (``_merge_memtables``, ``_merge_inputs``, ``_make_sst``) with its
  own span around each call.

After the window the store drains its background jobs, then every key
acknowledged since the load (warm-up and window) and ``readback_keys``
loaded keys never written since are read back through ``DB.get_batch``:
a version that a flush, a compaction or a migration lost or rolled back
shows there, whether or not a window read met it.  Checks, all limit 0:
``probe_mismatched_pairs`` and ``window_without_device_probe`` as in the
read-only cell; ``stale_or_foreign_reads``, window reads that broke the
versioned guarantees; ``lost_updates``, read-back keys not at their newest
acknowledged stamp; ``reused_or_foreign_stamps``, acknowledged writes
whose stamp is not one of their own (``kv_versions.Versions.bad_stamps``);
``window_without_write_path``, 1 unless the window completed a flush and
a compaction.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from chipbench import gen, harness
from chipbench.reference import kv_versions

base = harness.load_module(harness.HERE / "drivers" / "store_open_loop.py")
_open_loop = base._open_loop     # what ``calibrate.store_pace`` drives

SAMPLE = 64       # loaded keys read back in set-up


class State(base.State):
    def __init__(self, conf, traffic, seed, db):
        super().__init__(conf, traffic, seed, db)
        self.clock = 0
        self.acks = []        # (key, stamp, order, virtual time, in window)
        self.reads = []       # (issue order, keys, [(found, value)])
        self.merge_s = 0.0
        self.warmup_rounds = 0

    def tick(self) -> int:
        self.clock += 1
        return self.clock


def _require_load_stamps(st: State) -> None:
    keys = [int(k) for k in gen.rng_for(0, 6).choice(st.n, SAMPLE,
                                                     replace=False)]
    got = st.db.get_batch(keys)
    bad = sum(not f or v != kv_versions.load_stamp(k)
              for k, (f, v) in zip(keys, got))
    if bad:
        raise RuntimeError(
            f"{bad} of {SAMPLE} loaded keys read back without their load "
            "stamp: this store keeps no versioned values")


def _watch_writes(st: State) -> None:
    tree = st.db.tree
    real_put, real_get_batch = tree.put, tree.get_batch

    def put(key, value=None, tombstone=False, tenant=None):
        yield from real_put(key, value, tombstone, tenant)
        st.acks.append((int(key), value, st.tick(), st.db.sim.now,
                        st.recording))

    def get_batch(keys):
        issued = st.tick()
        res = yield from real_get_batch(keys)
        if st.recording:
            st.reads.append((issued, list(keys), list(res)))
        return res

    def timed(fn):
        def call(*args, **kw):
            t0 = time.perf_counter()
            with harness.span("merge"):
                out = fn(*args, **kw)
            if st.recording:
                st.merge_s += time.perf_counter() - t0
            return out
        return call

    tree.put, tree.get_batch = put, get_batch
    for name in ("_merge_memtables", "_merge_inputs", "_make_sst"):
        setattr(tree, name, timed(getattr(tree, name)))


def _next_flush_compacts(tree) -> bool:
    """L0 holds at least half its target, so the next flush takes it over
    and starts a compaction; the memtables hold at least a memtable of
    keys, so the window's updates bring that flush on.  These mirror the
    tree's own triggers (``LSMConfig``'s memtable size and L0 target): a
    change to them moves the phase at which the window starts."""
    cfg = tree.cfg
    pending = len(tree.memtable) + sum(len(m) for m in tree.immutables)
    return (tree.level_size(0) >= cfg.target_of(0) / 2
            and pending >= cfg.memtable_max_objs)


def _warm_write_path(st: State) -> None:
    """The mix in rounds of ``warmup_wall_s`` until a flush and a
    compaction have completed since the load and the next flush will start
    a compaction.  A flush takes two memtables of distinct keys and a
    compaction two flushes, more updates than a window holds, so where the
    window starts in that cycle decides whether it holds the write path.
    Each round draws its keys from an op stream of its own: rounds of one
    stream draw the same keys again."""
    tree, t = st.db.tree, st.traffic
    f0, c0 = tree.stats["flushes"], tree.stats["compactions"]
    step = t["warmup_wall_s"] * t["virtual_s_per_wall_s"]
    try:
        while (tree.stats["flushes"] == f0
               or tree.stats["compactions"] == c0
               or not _next_flush_compacts(tree)):
            if st.warmup_rounds >= int(t["warmup_max_rounds"]):
                raise RuntimeError(
                    f"write path not primed in {st.warmup_rounds} warm-up "
                    "rounds")
            st.warmup_rounds += 1
            seed = int(t["warmup_seed"]) * 1000 + st.warmup_rounds
            st.traffic = dict(t, stream_seed=seed)
            base._open_loop(st, step, seed)
    finally:
        st.traffic = t


def setup(conf: Dict, traffic: Dict, seed: int) -> State:
    from repro.lsm import DB
    from repro.workloads.ycsb import run_load
    db = DB(conf["scheme"], base.scenario(conf))
    st = State(conf, traffic, seed, db)
    with harness.span("load"):
        run_load(db, n_keys=st.n, seed=int(conf["load_seed"]))
        db.flush_all()
    _require_load_stamps(st)
    base._watch(st)
    _watch_writes(st)
    with harness.span("warmup"):
        base._warm_probe_shapes(st)
        _warm_write_path(st)
    return st


def window(st: State, seconds: float, tracer: harness.Tracer) -> None:
    be = st.db.backend
    st.be_before = dict(be.stats)
    st.merge_s = 0.0
    base.window(st, seconds, tracer)
    st.be_after = dict(be.stats)


def _readback_keys(st: State, written: np.ndarray) -> np.ndarray:
    """Every key written since the load and ``readback_keys`` loaded keys
    never written since, shuffled together."""
    rng = gen.rng_for(st.seed, 5)
    count = int(st.traffic["readback_keys"])
    pool = rng.choice(st.n, min(st.n, count + len(written)), replace=False)
    untouched = pool[~np.isin(pool, written)][:count]
    return rng.permutation(np.concatenate([written, untouched]))


def finish(st: State) -> Dict:
    res = st.result
    ref = kv_versions.Versions(st.n)
    for key, stamp, order, _, _ in st.acks:
        ref.ack(key, stamp, order)
    stale = reads = 0
    for issued, keys, got in st.reads:
        for k, (found, value) in zip(keys, got):
            reads += 1
            stale += ref.read_is_wrong(k, issued, found, value)
    acked = np.unique(np.asarray([a[0] for a in st.acks], np.int64))
    window_calls = list(st.calls)

    # drain in-flight background work, then read back through the
    # store's batched read, with the probe's hit masks kept again
    st.db.drain()
    back = _readback_keys(st, acked)
    batch = int(st.traffic["read_batch"])
    st.calls, st.recording = [], True
    lost = 0
    for i in range(0, len(back), batch):
        chunk = [int(k) for k in back[i:i + batch]]
        got = st.db.get_batch(chunk)
        lost += sum(not f or v != ref.newest(k)
                    for k, (f, v) in zip(chunk, got))
    st.recording = False

    mismatched = 0
    for _, _, _, k, (lo, hi, off, nw, bits), out in window_calls + st.calls:
        want = kv_versions.probe_pairs(lo, hi, off, nw, bits, k)
        got = np.asarray(out)[:len(lo)].astype(bool)
        mismatched += int(np.count_nonzero(got != want))

    b, a = st.before, st.after
    bb, ba = st.be_before, st.be_after
    delta = {key: a[key] - b[key] for key in (
        "flushes", "compactions", "puts_acked", "gets", "filter_probes",
        "probe_calls", "probe_image_uploads")}
    written = {key: ba[key] - bb[key] for key in ba
               if key.startswith("write_bytes.")}
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
            {"name": "window_without_device_probe",
             "value": int(not window_calls), "limit": 0},
            {"name": "stale_or_foreign_reads", "value": int(stale),
             "limit": 0},
            {"name": "lost_updates", "value": int(lost), "limit": 0},
            {"name": "reused_or_foreign_stamps", "value": ref.bad_stamps,
             "limit": 0},
            {"name": "window_without_write_path",
             "value": int(not (delta["flushes"] and delta["compactions"])),
             "limit": 0},
        ],
        "layer": {
            "counters": {
                "filter_probes": delta["filter_probes"],
                "gets": delta["gets"],
                "puts_acked": delta["puts_acked"],
                "write_bytes": written["write_bytes.ssd"]
                + written["write_bytes.hdd"],
                "object_bytes": int(st.conf["object_bytes"])},
            "merge_s": st.merge_s,
            "probe_call_s": [c[1] for c in window_calls],
            "traced_probe_calls": traced,
        },
        "info": {
            "window_s": st.window_s,
            "sim_ops_per_virtual_s": res.throughput,
            "sim_latency_p99_virtual_s": res.latency_p["p99"],
            "ops_completed": completed, "reads_checked": reads,
            "keys_written": int(len(acked)),
            "readback_keys": int(len(back)),
            "warmup_rounds": st.warmup_rounds,
            "merge_s": st.merge_s,
            **{k: v for k, v in delta.items()
               if k not in ("gets", "filter_probes")},
            **written,
        },
    }
