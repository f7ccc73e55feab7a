"""LSM-tree correctness: model-based property tests + structural invariants.

The hypothesis-driven property test only runs when the package is
installed; a deterministic randomized fallback keeps the dict-model
invariant covered either way.
"""
from dataclasses import replace

import numpy as np
import pytest

from conftest import tiny_scenario
from repro.lsm import DB
from repro.lsm.block_cache import BlockCache
from repro.zoned.device import MiB

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------
# model-based property test: the store behaves like a dict
# ---------------------------------------------------------------------
def _check_ops_against_model(ops):
    db = DB("HHZS", tiny_scenario(), store_values=True)
    model = {}
    for op, key in ops:
        if op == "put":
            val = b"v%d" % key
            db.put(key, val)
            model[key] = val
        elif op == "del":
            db.delete(key)
            model.pop(key, None)
        else:
            found, val = db.get(key)
            assert found == (key in model)
            if found:
                assert val == model[key]
    db.drain()
    for key in list(model)[:50]:
        found, val = db.get(key)
        assert found and val == model[key]


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(ops=st.lists(
        st.tuples(st.sampled_from(["put", "get", "del"]),
                  st.integers(min_value=0, max_value=400)),
        min_size=50, max_size=400))
    def test_store_matches_dict_model(ops):
        _check_ops_against_model(ops)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_store_matches_dict_model_deterministic(seed):
    """Fallback for environments without hypothesis: fixed-seed op streams."""
    rng = np.random.default_rng(seed)
    ops = [(("put", "get", "del")[int(rng.integers(3))],
            int(rng.integers(0, 400))) for _ in range(300)]
    _check_ops_against_model(ops)


# ---------------------------------------------------------------------
def _load(db, n, seed=0):
    for k in np.random.default_rng(seed).permutation(n):
        db.put(int(k), b"v%d" % k)
    db.drain()


def test_structural_invariants_after_compaction(any_db):
    db = any_db
    _load(db, 4000)
    t = db.tree
    for lvl in range(1, len(t.levels)):
        ssts = sorted(t.levels[lvl], key=lambda s: s.min_key)
        for s in ssts:
            assert np.all(np.diff(s.keys.astype(np.int64)) > 0), \
                "keys sorted+unique inside SST"
        for a, b in zip(ssts, ssts[1:]):
            assert a.max_key < b.min_key, f"L{lvl} ranges must be disjoint"
    # level byte accounting matches reality
    for lvl, lb in enumerate(t.level_sizes()):
        assert lb == sum(s.size_bytes for s in t.levels[lvl])


def test_zone_accounting_no_leaks(any_db):
    db = any_db
    _load(db, 3000)
    be = db.backend
    # every non-empty SSD zone has an owner; every SST's zones belong to it
    for z in db.ssd.zones:
        if z.write_ptr > 0 and z.zid not in be.reserve_zids:
            assert z.owner is not None
    for sst in be.ssts.values():
        dev = be.device_of(sst.tier)
        for z in sst.zones:
            assert z.owner == f"sst:{sst.sid}"
            assert dev.zones[z.zid] is z


def test_concurrent_burst_keeps_levels_disjoint():
    """Regression: while one L0 compaction ran, a second one could start
    over the leftover (overlapping) L0 files and install overlapping L1
    SSTs — the read path then returned stale versions."""
    db = DB("HHZS", tiny_scenario(), store_values=True)
    rng = np.random.default_rng(7)
    ops = [(int(k), b"v%d-%d" % (k, i))
           for i, k in enumerate(rng.integers(0, 250, size=500))]
    for k, v in ops:               # open-loop burst: compactions overlap
        db.submit(db.tree.put(k, v))
    db.drain()
    model = {}
    for k, v in ops:
        model[k] = v
    for lvl in range(1, len(db.tree.levels)):
        ssts = sorted(db.tree.levels[lvl], key=lambda s: s.min_key)
        for a, b in zip(ssts, ssts[1:]):
            assert a.max_key < b.min_key, \
                f"L{lvl} ranges overlap: {a.sid} and {b.sid}"
    for k in sorted(model):
        assert db.get(k) == (True, model[k])


def test_freed_zones_wake_writers_only_after_outputs_install():
    """Regression: a compaction freed its inputs' zones before installing
    its outputs.  Freeing wakes WAL-stalled writers at once; one of them
    rotated a memtable and picked an L0 compaction that could not see the
    missing L1 outputs, then installed L1 files over them — loaded keys
    read back as absent while reads ran beside the load's backlog."""
    from repro.workloads.ycsb import READ, YCSB, OpStream, run_load
    db = DB("HHZS", tiny_scenario())
    run_load(db, n_keys=3000, seed=0)
    db.flush_all()
    stream = OpStream(db, YCSB["C"], 1024, 3000, seed=2)
    keys = [stream.resolve(READ, int(r)) for r in stream.ops.args]
    for i in range(0, len(keys), 256):
        res = db.get_batch(keys[i:i + 256])
        assert [k for k, (f, _) in zip(keys[i:i + 256], res) if not f] == []
        for lvl in range(1, len(db.tree.levels)):
            ssts = sorted(db.tree.levels[lvl], key=lambda s: s.min_key)
            for a, b in zip(ssts, ssts[1:]):
                assert a.max_key < b.min_key, \
                    f"L{lvl} ranges overlap: {a.sid} and {b.sid}"


def test_overwrite_returns_latest():
    db = DB("HHZS", tiny_scenario(), store_values=True)
    for ver in range(5):
        for k in range(0, 500, 3):
            db.put(k, b"v%d-%d" % (k, ver))
    db.drain()
    for k in range(0, 500, 30):
        found, val = db.get(k)
        assert found and val == b"v%d-4" % k


def test_tombstones_survive_compaction():
    db = DB("B3", tiny_scenario(), store_values=True)
    _load(db, 2000)
    for k in range(0, 2000, 2):
        db.delete(k)
    db.drain()
    assert not db.get(100)[0]
    assert db.get(101)[0]


def test_scan_counts():
    db = DB("HHZS", tiny_scenario(), store_values=True)
    _load(db, 2000)
    seen = db.scan(500, 40)
    assert seen >= 40          # every key in [500, 540) exists


def test_post_recovery_l0_reads_survive_list_reorder():
    """Regression: `get` trusted L0 *list position* (reversed()) for
    recency while compaction/scan sort by -birth.  ``reopen_gen``
    installs L0 in ascending-sid order — accidentally newest-last — but
    nothing guarantees that, so reads must order L0 candidates by birth,
    not by list position."""
    sc = tiny_scenario()
    big = int(100 * MiB)            # L0 target huge: no compaction
    sc = replace(sc, lsm=replace(sc.lsm, level_targets=(big,) * 5))
    db = DB("HHZS", sc, store_values=True)
    for k in range(40):
        db.put(k, b"old-%d" % k)
    db.flush_all()
    for k in range(40):
        db.put(k, b"new-%d" % k)
    db.flush_all()
    db.drain()
    db.crash()
    db.reopen()
    l0 = db.tree.levels[0]
    assert len(l0) >= 2 and not any(db.tree.levels[i]
                                    for i in range(1, len(db.tree.levels)))
    # read back under adversarial list orders (newest-first is the one a
    # reversed()-based read path gets exactly backwards)
    for perm in (sorted(l0, key=lambda s: -s.birth),
                 sorted(l0, key=lambda s: s.birth)):
        db.tree.levels[0] = list(perm)
        for k in range(40):
            assert db.get(k) == (True, b"new-%d" % k), \
                "stale read: L0 recency must come from birth, not list order"


def test_zero_capacity_cache_fires_no_evictions():
    """Regression: insert() into a capacity<=0 cache fired on_evict for a
    block that was never cached."""
    evicted = []
    bc = BlockCache(0, on_evict=lambda sid, blk: evicted.append((sid, blk)))
    for i in range(16):
        bc.insert(7, i)
        assert not bc.get(7, i)
    assert not evicted and len(bc) == 0


def test_cacheless_config_emits_no_cache_hints():
    """Integration for the same bug: with block_cache_blocks=0 under a
    hint-driven scheme, reads must produce zero cache-hint traffic and
    zero SSD cache admissions."""
    sc = tiny_scenario()
    sc = replace(sc, lsm=replace(sc.lsm, block_cache_blocks=0))
    db = DB("HHZS", sc, store_values=True)
    _load(db, 2000)
    hints = []
    orig = db.tree.block_cache.on_evict
    db.tree.block_cache.on_evict = \
        lambda sid, blk: (hints.append((sid, blk)), orig(sid, blk))
    for k in range(0, 2000, 7):
        assert db.get(k)[0]
    db.drain()
    assert not hints
    assert db.backend.cache.admitted == 0


def test_wal_group_commit_batches_writers():
    db = DB("HHZS", tiny_scenario(), store_values=True)
    sim, tree = db.sim, db.tree
    procs = [sim.process(tree.put(k)) for k in range(64)]
    for p in procs:
        sim.run_until(p)
    # group commit: far fewer WAL I/Os than appends
    assert db.ssd.counters.write_ops < 64
