"""Versioned values through the write path: every write of a YCSB A run
stores a stamp (``repro.workloads.ycsb.stamp``), and each answer, and a
read-back after the store drains, is compared with a plain versioned map
across flushes, compactions and migrations; two planted faults (inserts
dropped after their WAL acknowledgement, compactions that keep an older
version) are caught; stamps survive a crash through WAL replay."""
from bisect import bisect_left
from dataclasses import replace

import numpy as np
import pytest

from conftest import tiny_scenario
from repro.lsm import DB
from repro.lsm.tree import LSMTree
from repro.workloads import YCSB, PoissonArrivals, run_load, run_open_loop
from repro.workloads.ycsb import stamp

N = 3000


class _Recorder:
    """Wraps every tree's ``put`` and ``get_batch``: each acknowledgement
    (key, stamp) and each batch read (when issued, keys, answers), on one
    counter that orders them."""

    def __init__(self, monkeypatch):
        self.clock, self.acks, self.reads = 0, [], []
        real_put, real_get_batch = LSMTree.put, LSMTree.get_batch

        def put(tree, key, value=None, tombstone=False, tenant=None):
            yield from real_put(tree, key, value, tombstone, tenant)
            self.acks.append((int(key), value, self._tick()))

        def get_batch(tree, keys):
            issued = self._tick()
            res = yield from real_get_batch(tree, keys)
            self.reads.append((issued, list(keys), list(res)))
            return res

        monkeypatch.setattr(LSMTree, "put", put)
        monkeypatch.setattr(LSMTree, "get_batch", get_batch)

    def _tick(self):
        self.clock += 1
        return self.clock


class _Versions:
    """Plain versioned map: each key's stamps in acknowledgement order."""

    def __init__(self, acks):
        self.orders, self.stamps = {}, {}
        for key, value, order in acks:
            self.orders.setdefault(key, []).append(order)
            self.stamps.setdefault(key, []).append(value)

    def newest(self, key, before=None):
        orders = self.orders.get(key, [])
        i = len(orders) if before is None else bisect_left(orders, before)
        return self.stamps[key][i - 1] if i else None

    def allowed(self, key, issued):
        """The newest version acknowledged before the read was issued, or
        one acknowledged while it ran."""
        orders = self.orders.get(key, [])
        i = bisect_left(orders, issued)
        return {self.newest(key, issued)} | set(self.stamps.get(key, [])[i:])


def _wrong_reads(rec):
    model = _Versions(rec.acks)
    return sum((v if f else None) not in model.allowed(k, issued)
               for issued, keys, res in rec.reads
               for k, (f, v) in zip(keys, res))


def _lost(db, rec):
    """Loaded keys whose value read back now is not their newest stamp."""
    model = _Versions(rec.acks)
    got = db.get_batch(list(range(N)))
    return sum((v if f else None) != model.newest(k)
               for k, (f, v) in enumerate(got))


def _run_a(db, **kw):
    return run_open_loop(db, YCSB["A"], PoissonArrivals(60.0), duration=40.0,
                         n_keys=N, read_batch=8, max_concurrency=16, seed=3,
                         **kw)


def _loaded_db():
    db = DB("HHZS", tiny_scenario(), store_values=True)
    run_load(db, n_keys=N)
    db.flush_all()
    return db


def test_ycsb_a_answers_and_readback_match_a_plain_versioned_map(
        monkeypatch):
    rec = _Recorder(monkeypatch)
    db = _loaded_db()
    tree0, be0 = dict(db.tree.stats), dict(db.backend.stats)
    res = _run_a(db)
    db.drain()
    assert res.n_measured > 2000
    assert db.tree.stats["flushes"] > tree0["flushes"]
    assert db.tree.stats["compactions"] > tree0["compactions"]
    assert db.backend.stats["write_bytes.migration"] > \
        be0["write_bytes.migration"]
    # every write carries its own stamp: the load's, then the run's
    stamps = [v for _, v, _ in rec.acks]
    assert len(set(stamps)) == len(stamps)
    assert all(v == stamp(k, 0) for k, v, _ in rec.acks[:N])
    assert len(rec.reads) > 100
    assert _wrong_reads(rec) == 0
    assert _lost(db, rec) == 0


def test_write_counters_split_by_stream_and_by_device():
    db = _loaded_db()
    db.drain()                   # nothing in flight across the snapshots
    be, tree = db.backend, db.tree
    b0, t0 = dict(be.stats), dict(tree.stats)
    ssd0, hdd0 = db.ssd.counters.write_bytes, db.hdd.counters.write_bytes
    _run_a(db)
    db.drain()
    d = {k: be.stats[k] - b0[k] for k in b0 if k.startswith("write_bytes.")}
    streams = sum(d["write_bytes." + s]
                  for s in ("wal", "flush", "compaction", "migration"))
    assert streams == d["write_bytes.ssd"] + d["write_bytes.hdd"] > 0
    # the SSD also takes the hinted cache's fills, which no stream counts
    assert d["write_bytes.ssd"] <= db.ssd.counters.write_bytes - ssd0
    assert d["write_bytes.hdd"] == db.hdd.counters.write_bytes - hdd0
    acked = tree.stats["puts_acked"] - t0["puts_acked"]
    assert acked == tree.stats["puts"] - t0["puts"] > 0
    assert d["write_bytes.wal"] == acked * tree.cfg.obj_size


def test_store_without_values_writes_no_stamps():
    sc = tiny_scenario()
    db = DB("HHZS", replace(sc, lsm=replace(sc.lsm, store_values=False)))
    run_load(db, n_keys=600)
    db.flush_all()
    _run_a(db)
    assert all(v is None for _, v in db.get_batch(list(range(600))))
    assert all(s.values is None for lvl in db.tree.levels for s in lvl)


def _drop_every_64th_insert(monkeypatch):
    """Every 64th write after the load is acknowledged once its WAL record
    is durable, but never reaches the memtable."""
    real = LSMTree.put
    count = {"n": 0}

    def put(tree, key, value=None, tombstone=False, tenant=None):
        if value is None or value & 0xFFFFFFFF == 0:
            return (yield from real(tree, key, value, tombstone, tenant))
        count["n"] += 1
        if count["n"] % 64:
            return (yield from real(tree, key, value, tombstone, tenant))
        yield from tree.backend.wal_append(tree.cfg.obj_size)
        tree.stats["puts_acked"] += 1

    monkeypatch.setattr(LSMTree, "put", put)


def _keep_older_version(monkeypatch):
    """Of the keys a compaction's inputs hold twice or more, every 64th
    keeps the older version."""
    real = LSMTree._merge_inputs

    def merge(tree, level, inputs):
        keys, tombs, values, comp = real(tree, level, inputs)
        versions = {}
        for s in inputs:
            for k, v in zip(s.keys.tolist(), s.values):
                versions.setdefault(k, []).append(v)
        at = {k: j for j, k in enumerate(keys.tolist())}
        twice = sorted(k for k, vs in versions.items()
                       if len(vs) > 1 and k in at)
        for k in twice[::64]:
            values[at[k]] = min(versions[k])
        return keys, tombs, values, comp

    monkeypatch.setattr(LSMTree, "_merge_inputs", merge)


@pytest.mark.parametrize("fault", [_drop_every_64th_insert,
                                   _keep_older_version])
def test_planted_fault_is_caught(fault, monkeypatch):
    fault(monkeypatch)
    rec = _Recorder(monkeypatch)
    db = _loaded_db()
    _run_a(db)
    db.drain()
    lost = _lost(db, rec)
    if fault is _drop_every_64th_insert:
        assert lost > 0
    else:
        assert lost + _wrong_reads(rec) > 0


def test_stamps_survive_crash_and_wal_replay(monkeypatch):
    rec = _Recorder(monkeypatch)
    db = _loaded_db()
    _run_a(db, drain=False)          # stop with writes in the memtables
    db.crash()
    assert db.reopen()["replayed_records"] > 0
    replayed = {k: v for m in db.tree.immutables
                for k, (_, v) in m.data.items()}
    assert replayed and all(v >> 32 == k for k, v in replayed.items())
    assert _lost(db, rec) == 0


def test_flush_and_compaction_merge_values_with_their_keys():
    db = DB("HHZS", tiny_scenario(), store_values=True)
    rng = np.random.default_rng(5)
    want = {}
    for i, k in enumerate(rng.integers(0, 400, size=1500)):
        db.put(int(k), stamp(int(k), i + 1))
        want[int(k)] = stamp(int(k), i + 1)
    db.drain()
    assert db.tree.stats["compactions"] > 0
    for lvl in db.tree.levels:
        for s in lvl:
            assert len(s.values) == s.num_objs
            assert all(v >> 32 == k for k, v in zip(s.keys.tolist(),
                                                     s.values))
    got = db.get_batch(sorted(want))
    assert [v for _, v in got] == [want[k] for k in sorted(want)]
