"""Serving-stack correctness suite: paged KV zones, tier managers,
placement policies and the open-loop serving runner.

Layout mirrors the stack:

* PagedPool zone semantics — alloc/reset conservation, double-free
  detection, write/read round-trips, partial-zone migration;
* HHZSKVManager — demand-fits placement, cold-only demotion,
  all-or-nothing promotion, §3.5 prefix-cache consistency (each
  regression test here encodes a bug found in the zone-accounting
  audit: the pre-fix code fails it);
* policy baselines — static admission reservations, LRU recency
  eviction;
* run_serving differentials — every policy under ``verify="step"``
  (full KV readback each decode step), cross-policy arrival/churn
  equality, byte-identical rows with telemetry attached;
* a property test over random submit/step/pause/release schedules
  (hypothesis when installed, fixed-seed fallback otherwise — the
  convention of tests/test_lsm.py);
* jax-gated engine tests (`_gather_kv` vs a dense reference; the e2e
  decode equivalence stays behind ``-m slow``).

Everything above the jax section runs honestly on the no-jax CI leg:
the pools fall back to numpy and the serving runner never imports the
model stack.
"""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from repro.serving import (HHZSKVManager, LRUKVManager, PagedPool,
                           StaticHBMManager, make_manager)
from repro.workloads import TenantSpec
from repro.workloads.serving import (ServingCosts, ServingPool,
                                     ServingWorkload, _payload,
                                     build_serving_grid, run_serving,
                                     serving_arrivals)

try:
    import jax
    import jax.numpy as jnp
    HAVE_JAX = True
except ImportError:
    jax = jnp = None
    HAVE_JAX = False

try:
    from hypothesis import HealthCheck, example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

L, KV, D = 2, 2, 8
SHAPE = (L, KV, D)


def _pools(hbm=4, host=16, ppz=2, ps=4, materialize=True):
    mk = lambda name, zones, host_: PagedPool(
        name, L, zones, ppz, ps, KV, D, host=host_, materialize=materialize)
    return mk("hbm", hbm, False), mk("host", host, True)


def _fill(mgr, seq, tokens, materialized=True):
    for _ in range(tokens):
        z = mgr.writable_zone(seq)
        if materialized:
            pl = _payload(seq.sid, seq.length, SHAPE)
            mgr.pool_of(seq).write_token(z, pl, pl)
        else:
            mgr.pool_of(seq).write_token(z)
        seq.length += 1


# ======================================================================
# PagedPool zone semantics
# ======================================================================
def test_alloc_reset_conservation():
    hbm, _ = _pools()
    zs = [hbm.alloc_zone(owner=i) for i in range(4)]
    assert all(z is not None for z in zs)
    assert hbm.num_free() == 0 and hbm.alloc_zone(owner=9) is None
    for z in zs:
        hbm.reset_zone(z)
    assert hbm.num_free() == 4
    assert all(z.owner is None and z.write_ptr == 0 for z in hbm.zones)


def test_double_reset_raises():
    """Audit regression: a double reset would enqueue the zone on the
    free list twice and hand it to two owners later."""
    hbm, _ = _pools()
    z = hbm.alloc_zone(owner=0)
    hbm.reset_zone(z)
    with pytest.raises(RuntimeError, match="reset twice"):
        hbm.reset_zone(z)
    assert hbm.num_free() == 4          # not double-counted


def test_corrupted_free_list_detected():
    hbm, _ = _pools()
    hbm.zones[hbm._free[0]].owner = 7   # corrupt: free zone with an owner
    with pytest.raises(RuntimeError, match="accounting corrupted"):
        hbm.alloc_zone(owner=1)


def test_write_read_roundtrip():
    hbm, _ = _pools()
    z = hbm.alloc_zone(owner=0)
    for pos in range(8):                # ppz*ps = full zone
        pl = _payload(0, pos, SHAPE)
        hbm.write_token(z, pl, pl)
    assert z.remaining(hbm.page_size) == 0
    for pos in range(8):
        k, v = hbm.read_token(z, pos)
        want = _payload(0, pos, SHAPE)
        np.testing.assert_array_equal(k, want)
        np.testing.assert_array_equal(v, want)


def test_read_unwritten_token_raises():
    hbm, _ = _pools()
    z = hbm.alloc_zone(owner=0)
    pl = _payload(0, 0, SHAPE)
    hbm.write_token(z, pl, pl)
    with pytest.raises(IndexError):
        hbm.read_token(z, 1)


def test_write_past_zone_capacity_rejected():
    hbm, _ = _pools()
    z = hbm.alloc_zone(owner=0)
    pl = _payload(0, 0, SHAPE)
    for _ in range(8):
        hbm.write_token(z, pl, pl)
    with pytest.raises(AssertionError):
        hbm.write_token(z, pl, pl)


def test_accounting_only_pool():
    hbm, _ = _pools(materialize=False)
    z = hbm.alloc_zone(owner=0)
    hbm.write_token(z)                  # no tensors needed
    assert z.write_ptr == 1
    assert hbm.bytes_written == hbm.token_bytes
    with pytest.raises(ValueError, match="no data"):
        hbm.read_token(z, 0)


def test_materialized_pool_requires_tensors():
    hbm, _ = _pools()
    z = hbm.alloc_zone(owner=0)
    with pytest.raises(ValueError, match="needs K/V"):
        hbm.write_token(z)


def test_copy_zone_partial_fill():
    """Audit regression: only pages covered by the source write pointer
    move, and the bytes charged are the written tokens — a half-full
    zone must not pay for (or read) its empty tail."""
    hbm, host = _pools()
    src = hbm.alloc_zone(owner=0)
    for pos in range(5):                # 5 of 8 tokens -> 2 pages touched
        pl = _payload(0, pos, SHAPE)
        hbm.write_token(src, pl, pl)
    dst = host.alloc_zone(owner=0)
    moved = host.copy_zone_from(hbm, src, dst)
    assert moved == 5 * hbm.token_bytes
    assert dst.write_ptr == 5
    for pos in range(5):
        k, _ = host.read_token(dst, pos)
        np.testing.assert_array_equal(k, _payload(0, pos, SHAPE))


def test_copy_zone_page_size_mismatch_raises():
    hbm, _ = _pools(ps=4)
    other = PagedPool("odd", L, 2, 2, 8, KV, D, host=True)
    src = other.alloc_zone(owner=0)
    dst = hbm.alloc_zone(owner=0)
    with pytest.raises(ValueError, match="page-size mismatch"):
        hbm.copy_zone_from(other, src, dst)


def test_copy_zone_overflow_raises():
    big = PagedPool("big", L, 2, 4, 4, KV, D, host=True)
    small = PagedPool("small", L, 2, 2, 4, KV, D, host=True)
    src = big.alloc_zone(owner=0)
    pl = _payload(0, 0, SHAPE)
    for _ in range(12):                 # 12 tokens > small's 8-token zone
        big.write_token(src, pl, pl)
    dst = small.alloc_zone(owner=0)
    with pytest.raises(ValueError, match="overflow"):
        small.copy_zone_from(big, src, dst)


def test_num_free_matches_owner_recount():
    hbm, _ = _pools(hbm=6)
    rng = np.random.default_rng(0)
    held = []
    for _ in range(200):
        if held and rng.random() < 0.5:
            hbm.reset_zone(held.pop(rng.integers(len(held))))
        else:
            z = hbm.alloc_zone(owner=int(rng.integers(100)))
            if z is not None:
                held.append(z)
        free_ids = list(hbm._free)
        assert len(free_ids) == len(set(free_ids))
        assert hbm.num_free() == sum(1 for z in hbm.zones
                                     if z.owner is None)


# ======================================================================
# HHZSKVManager: placement, migration, prefix cache
# ======================================================================
def test_pressure_pushes_sequences_to_host():
    hbm, host = _pools(hbm=2)
    mgr = HHZSKVManager(hbm, host, cache_zones=0)
    seqs = []
    for sid in range(4):
        seq = mgr.on_prefill(sid, tokens=8)
        _fill(mgr, seq, 8)
        seqs.append(seq)
    assert "host" in {s.tier for s in seqs}
    owned = sum(len(s.zones) for s in mgr.seqs.values())
    used = sum(1 for p in (hbm, host) for z in p.zones
               if z.owner not in (None, -1))
    assert owned == used


def test_release_reclaims_zones():
    hbm, host = _pools()
    mgr = HHZSKVManager(hbm, host, cache_zones=0)
    seq = mgr.on_prefill(0, tokens=10)
    _fill(mgr, seq, 10)
    free_before = hbm.num_free()
    mgr.release(0)
    assert hbm.num_free() > free_before
    assert 0 not in mgr.seqs


def test_prefill_demotes_cold_not_active():
    """§3.3 write-guided placement: the hot prefill claims HBM by
    demoting a *cold* resident; residents active this step stay put.
    (3 zones: one per resident plus the active one's growth demand —
    §3.3 reserves that slack, so only the cold zone is reclaimable.)"""
    hbm, host = _pools(hbm=3)
    mgr = HHZSKVManager(hbm, host, cache_zones=0)
    cold = mgr.on_prefill(0, tokens=8)
    _fill(mgr, cold, 8)
    warm = mgr.on_prefill(1, tokens=8)
    _fill(mgr, warm, 8)
    mgr.tick([1])                       # seq 1 active, seq 0 cold
    fresh = mgr.on_prefill(2, tokens=8)
    assert fresh.tier == "hbm"
    assert mgr.seqs[0].tier == "host"   # the cold one paid
    assert mgr.seqs[1].tier == "hbm"    # the active one did not


def test_prefill_lands_on_host_when_only_active_residents():
    hbm, host = _pools(hbm=2)
    mgr = HHZSKVManager(hbm, host, cache_zones=0)
    for sid in range(2):
        _fill(mgr, mgr.on_prefill(sid, tokens=8), 8)
    mgr.tick([0, 1])                    # both residents active
    fresh = mgr.on_prefill(2, tokens=8)
    assert fresh.tier == "host"
    assert all(mgr.seqs[s].tier == "hbm" for s in (0, 1))


def test_promotion_is_all_or_nothing():
    """Audit regression: a promotion that cannot reserve every
    destination zone must abort cleanly — the pre-fix code freed host
    zones one by one and stranded the sequence on partial copies."""
    hbm, host = _pools(hbm=2)
    mgr = HHZSKVManager(hbm, host, cache_zones=1)   # 1 free HBM zone left
    seq = mgr.on_prefill(0, tokens=8)
    _fill(mgr, seq, 8)
    mgr._seq_to_host(seq)
    _fill(mgr, seq, 8)                  # grow to 2 host zones
    assert seq.tier == "host" and len(seq.zones) == 2
    free_hbm, free_host = hbm.num_free(), host.num_free()
    assert mgr._promote(seq) == 0       # 2 zones needed, 1 free
    assert seq.tier == "host" and len(seq.zones) == 2
    assert all(z.owner == 0 for z in seq.zones)
    assert (hbm.num_free(), host.num_free()) == (free_hbm, free_host)


def test_demote_promote_demote_no_leak():
    hbm, host = _pools(hbm=4)
    mgr = HHZSKVManager(hbm, host, cache_zones=1)
    seq = mgr.on_prefill(0, tokens=16)
    _fill(mgr, seq, 16)
    total_free = hbm.num_free() + host.num_free()
    for _ in range(3):
        mgr._seq_to_host(seq)
        assert seq.tier == "host"
        assert mgr._promote(seq) > 0
        assert seq.tier == "hbm"
        assert hbm.num_free() + host.num_free() == total_free
    for pos in range(16):               # data survived six migrations
        k, _ = _read_seq(mgr, seq, pos)
        np.testing.assert_array_equal(k, _payload(0, pos, SHAPE))


def _read_seq(mgr, seq, pos):
    pool = mgr.pool_of(seq)
    for z in seq.zones:
        if pos < z.write_ptr:
            return pool.read_token(z, pos)
        pos -= z.write_ptr
    raise IndexError(pos)


def test_cache_admitted_before_source_reset():
    """Audit regression (§3.5 ordering): the prefix copy must happen
    while the demoting sequence's HBM zones still hold valid data —
    admitting after the reset cached an empty zone."""
    hbm, host = _pools(hbm=4)
    mgr = HHZSKVManager(hbm, host, cache_zones=1)
    seq = mgr.on_prefill(0, tokens=8)
    _fill(mgr, seq, 8)
    mgr._seq_to_host(seq)
    cz = mgr.prefix_cache[0]
    assert cz.write_ptr == 8            # not an empty post-reset copy
    for pos in range(8):
        k, _ = mgr.hbm.read_token(cz, pos)
        np.testing.assert_array_equal(k, _payload(0, pos, SHAPE))
    assert seq.prefix_cached


def test_cache_fifo_eviction_reuses_evicted_zone():
    """Audit regression: the FIFO evictee's zone (not an occupancy-indexed
    one) must back the new entry, and the evicted sequence's
    ``prefix_cached`` flag must clear."""
    hbm, host = _pools(hbm=8)
    mgr = HHZSKVManager(hbm, host, cache_zones=2)
    for sid in range(3):
        seq = mgr.on_prefill(sid, tokens=8)
        _fill(mgr, seq, 8)
        mgr._seq_to_host(seq)
    assert 0 not in mgr.prefix_cache            # FIFO evicted the oldest
    assert not mgr.seqs[0].prefix_cached
    assert mgr.seqs[1].prefix_cached and mgr.seqs[2].prefix_cached
    zids = {z.zid for z in mgr.prefix_cache.values()}
    assert len(zids) == 2                        # no zone collision
    assert zids <= {z.zid for z in mgr.cache_pool}
    for sid in (1, 2):                           # survivors read back clean
        cz = mgr.prefix_cache[sid]
        for pos in range(cz.write_ptr):
            k, _ = mgr.hbm.read_token(cz, pos)
            np.testing.assert_array_equal(k, _payload(sid, pos, SHAPE))


def test_promote_drops_cache_entry():
    hbm, host = _pools(hbm=6)
    mgr = HHZSKVManager(hbm, host, cache_zones=1)
    seq = mgr.on_prefill(0, tokens=8)
    _fill(mgr, seq, 8)
    mgr._seq_to_host(seq)
    assert 0 in mgr.prefix_cache
    assert mgr._promote(seq) > 0
    assert 0 not in mgr.prefix_cache and not seq.prefix_cached


def test_residency_accounting():
    hbm, host = _pools(hbm=6)
    mgr = HHZSKVManager(hbm, host, cache_zones=1)
    seq = mgr.on_prefill(0, tokens=12)
    _fill(mgr, seq, 12)
    assert mgr.residency(seq) == (12, 0)
    mgr._seq_to_host(seq)
    h, c = mgr.residency(seq)
    assert h + c == 12
    assert h == min(mgr.prefix_cache[0].write_ptr, 12) == 8  # 1 zone cached
    assert mgr.stats["cache_hits"] >= 1


def test_preempt_stall_counter():
    hbm, host = _pools(hbm=2)
    mgr = HHZSKVManager(hbm, host, cache_zones=0)
    for sid in range(2):
        _fill(mgr, mgr.on_prefill(sid, tokens=8), 8)
    mgr.tick([0, 1])                    # both decoded this step
    before = mgr.stats["preempt_stalls"]
    assert mgr._demote_one(exclude=0)   # forced to evict an active seq
    assert mgr.stats["preempt_stalls"] == before + 1


# ======================================================================
# policy baselines
# ======================================================================
def test_static_admission_reservations():
    hbm, host = _pools(hbm=4)           # 4 zones x 8 tokens
    mgr = StaticHBMManager(hbm, host)
    assert mgr.admit(0, 16)             # 2 zones
    assert mgr.admit(1, 8)              # 1 zone
    assert not mgr.admit(2, 16)         # 2 zones > 4 - 3 outstanding
    assert mgr.admit(3, 8)              # the last zone
    for sid, toks in ((0, 16), (1, 8), (3, 8)):
        seq = mgr.on_prefill(sid, toks)
        _fill(mgr, seq, toks)           # reservations guarantee room
        assert seq.tier == "hbm"
    mgr.release(0)
    assert mgr.admit(4, 16)             # freed zones re-admittable


def test_static_never_migrates():
    hbm, host = _pools(hbm=4)
    mgr = StaticHBMManager(hbm, host)
    assert mgr.admit(0, 8)
    seq = mgr.on_prefill(0, 8)
    _fill(mgr, seq, 8)
    mgr.tick([0])
    assert seq.tier == "hbm"
    assert host.num_free() == 16        # host tier untouched
    assert mgr.stats["demotions"] == mgr.stats["promotions"] == 0


def test_lru_victim_is_least_recently_used():
    hbm, host = _pools(hbm=2)
    mgr = LRUKVManager(hbm, host)
    for sid in range(2):
        _fill(mgr, mgr.on_prefill(sid, tokens=8), 8)
    mgr.tick([1])                       # seq 0 goes stale
    mgr.tick([1])
    assert mgr._demote_one(exclude=-1)
    assert mgr.seqs[0].tier == "host"   # recency, not level, chose it
    assert mgr.seqs[1].tier == "hbm"


def test_lru_prefill_always_starts_in_hbm():
    hbm, host = _pools(hbm=2)
    mgr = LRUKVManager(hbm, host)
    for sid in range(2):
        _fill(mgr, mgr.on_prefill(sid, tokens=8), 8)
    mgr.tick([0, 1])                    # both residents active
    fresh = mgr.on_prefill(2, tokens=8)
    assert fresh.tier == "hbm"          # hint-blind: evicts actives anyway
    _fill(mgr, fresh, 8)
    assert "host" in {mgr.seqs[s].tier for s in (0, 1)}


def test_make_manager_dispatch():
    hbm, host = _pools()
    assert isinstance(make_manager("static", hbm, host), StaticHBMManager)
    hbm2, host2 = _pools()
    assert isinstance(make_manager("lru", hbm2, host2), LRUKVManager)
    hbm3, host3 = _pools()
    mgr = make_manager("hhzs", hbm3, host3, cache_zones=1)
    assert type(mgr) is HHZSKVManager
    with pytest.raises(ValueError, match="unknown serving policy"):
        make_manager("fifo", hbm, host)


# ======================================================================
# run_serving differentials
# ======================================================================
_TEST_WL = ServingWorkload(name="chat", prompt_med=24, prompt_max=64,
                           out_med=12, out_max=32, pause_prob=0.02,
                           pause_mean=2.0, slo_ttft=2.0)


def _run(policy, *, verify=False, materialize=False, duration=25.0,
         registry=None, sim=None, seed=3, hbm=6):
    arr = serving_arrivals(("poisson",), 2.0)[0]
    return run_serving(
        [TenantSpec("t0", _TEST_WL, arr, protected=True, slo_p99=2.0)],
        policy, pool=ServingPool(hbm_zones=hbm, host_zones=48),
        duration=duration, warmup=5.0, seed=seed, verify=verify,
        materialize=materialize, registry=registry, sim=sim)


@pytest.mark.parametrize("policy", ["static", "lru", "hhzs"])
def test_verify_step_differential(policy):
    """Full resident-KV readback after every decode step: any migration
    or cache admit that corrupts, drops or aliases a page fails here."""
    res = _run(policy, verify="step", materialize=True)
    r = res.rows[0]
    assert r["n_completed"] > 0
    if policy != "static":
        assert r["demote_pages"] > 0    # the differential saw migrations


def test_arrival_and_churn_streams_policy_independent():
    """The seeded draws (arrivals, lengths, pause churn) must not depend
    on the policy, or cross-policy comparisons are meaningless."""
    rows = {p: _run(p).rows[0] for p in ("lru", "hhzs")}
    for key in ("n_arrived", "admitted", "tokens_out", "pauses",
                "offered_rate"):
        assert rows["lru"][key] == rows["hhzs"][key], key


def test_all_admitted_sequences_complete_and_zones_return():
    from repro.zoned.sim import Sim
    sim = Sim()
    res = _run("hhzs", sim=sim)
    r = res.rows[0]
    assert r["n_completed"] == r["admitted"] == r["n_arrived"]
    assert r["rejected"] == 0
    spool = ServingPool(hbm_zones=6, host_zones=48)
    assert res.stats["hbm_free_zones"] == spool.hbm_zones - spool.cache_zones
    assert res.stats["host_free_zones"] == spool.host_zones


def test_static_conservation_under_rejection():
    res = _run("static", hbm=3, duration=40.0)
    r = res.rows[0]
    assert r["rejected"] > 0            # tiny pool must shed
    assert r["n_arrived"] == r["admitted"] + r["rejected"]
    assert r["n_completed"] == r["admitted"]
    assert r["hbm_hit_rate"] == 1.0     # never touches the host tier
    assert r["migrated_bytes"] == 0


def test_rows_byte_identical_with_telemetry():
    """Telemetry is pull-only: attaching the metrics registry must not
    change a single row byte (the grid-smoke CI invariant)."""
    from repro.obs.metrics import MetricsRegistry
    from repro.zoned.sim import Sim
    base = json.dumps(_run("hhzs").rows, sort_keys=True)
    sim = Sim()
    reg = MetricsRegistry(sim, 5.0)
    res = _run("hhzs", sim=sim, registry=reg)
    assert json.dumps(res.rows, sort_keys=True) == base
    reg.sample_now()
    tl = reg.timeline()
    assert any(s.startswith("serving.") for s in tl["series"])


def test_slo_columns_present():
    r = _run("hhzs").rows[0]
    assert r["slo_p99"] == 2.0
    assert isinstance(r["slo_met"], bool)
    assert r["goodput"] >= 0.0
    assert set(r["ttft_p"]) == {"p50", "p90", "p99", "p999", "p9999"}


def test_unknown_policy_and_arrival_rejected():
    arr = serving_arrivals(("poisson",), 1.0)[0]
    with pytest.raises(ValueError, match="unknown policy"):
        run_serving([TenantSpec("t", _TEST_WL, arr)], "mru")
    with pytest.raises(ValueError, match="unknown arrival"):
        serving_arrivals(("sawtooth",), 1.0)
    with pytest.raises(ValueError, match="materialize"):
        run_serving([TenantSpec("t", _TEST_WL, arr)], "hhzs", verify=True)


def test_serving_grid_cells_and_matrix_cell():
    matrix = build_serving_grid(
        ("lru", "hhzs"), ("poisson", "bursty"), (6, 8),
        rate=1.5, duration=15.0, warmup=3.0, workload=_TEST_WL)
    cells = matrix.cells()
    assert len(cells) == 2 * 2 * 2
    names = [c.name for c in cells]
    assert len(set(names)) == len(names)
    assert all(n.startswith("serving/") for n in names)
    _, rows = matrix.run_cell(cells[0])
    assert rows and all(r["cell"] == cells[0].name for r in rows)
    assert rows[0]["tiering"] == cells[0].policy


def test_serving_rows_pass_schema_lint():
    pytest.importorskip("benchmarks.validate_results")
    from benchmarks.validate_results import row_kind, validate_rows
    rows = _run("hhzs").rows
    for r in rows:
        r["cell"] = "serving/test"
    assert row_kind(rows[0]) == "serving"
    assert validate_rows(rows, "test") == []
    bad = dict(rows[0], n_arrived=rows[0]["n_arrived"] + 1)
    assert any("conservation" in e
               for e in validate_rows([bad], "test"))


# ======================================================================
# property test: random schedules keep zone accounting consistent
# ======================================================================
def _check_zone_invariants(mgr, hbm, host):
    for pool in (hbm, host):
        free = set(pool._free)
        assert len(free) == len(pool._free), "free-list duplicate"
        for z in pool.zones:
            assert (z.owner is None) == (z.zid in free), \
                f"{pool.name} zone {z.zid}: owner {z.owner} vs free list"
    seen = set()
    for sid, seq in mgr.seqs.items():
        pool = mgr.pool_of(seq)
        for z in seq.zones:
            assert pool.zones[z.zid] is z, "zone mapped in the wrong tier"
            assert z.owner == sid, \
                f"zone {z.zid} owned by {z.owner}, mapped by {sid}"
            key = (pool.name, z.zid)
            assert key not in seen, f"zone {key} mapped twice"
            seen.add(key)
    for z in mgr.cache_pool:
        assert z.owner == -1 and mgr.hbm.zones[z.zid] is z
    assert {z.zid for z in mgr.prefix_cache.values()} <= \
        {z.zid for z in mgr.cache_pool}


SCHEDULE_NEW_TOKENS = 4      # max_new_tokens of every scheduled sequence


def _apply_schedule(policy, ops):
    hbm, host = _pools(hbm=4, host=24, materialize=False)
    mgr = make_manager(policy, hbm, host, cache_zones=1)
    # as the engine does, admit prompt + max_new_tokens and stop decoding a
    # sequence once it reaches that committed total
    committed, live, next_sid = {}, [], 0
    for op, arg in ops:
        if op == "submit":
            tokens = 1 + arg % 20
            if not mgr.admit(next_sid, tokens + SCHEDULE_NEW_TOKENS):
                continue
            committed[next_sid] = tokens + SCHEDULE_NEW_TOKENS
            seq = mgr.on_prefill(next_sid, tokens)
            _fill(mgr, seq, tokens, materialized=False)
            live.append(next_sid)
            next_sid += 1
        elif op == "step" and live:
            active = live[:1 + arg % 4]
            mgr.tick(active)
            for sid in active:
                seq = mgr.seqs[sid]
                if seq.length < committed[sid]:
                    _fill(mgr, seq, 1, materialized=False)
        elif op == "rotate" and live:   # churn: demote the head manually
            live.append(live.pop(0))
        elif op == "release" and live:
            mgr.release(live.pop(arg % len(live)))
        _check_zone_invariants(mgr, hbm, host)
    for sid in live:
        mgr.release(sid)
    _check_zone_invariants(mgr, hbm, host)
    assert hbm.num_free() == 4 - len(mgr.cache_pool)
    assert host.num_free() == 24


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(policy=st.sampled_from(["static", "lru", "hhzs"]),
           ops=st.lists(
               st.tuples(st.sampled_from(["submit", "step", "rotate",
                                          "release"]),
                         st.integers(min_value=0, max_value=40)),
               min_size=5, max_size=80))
    # static: a step grew a full-zone sequence past its admitted budget
    @example(policy="static", ops=[("submit", 0), ("submit", 7),
                                   ("submit", 0), ("submit", 0),
                                   ("step", 1)])
    def test_zone_accounting_property(policy, ops):
        _apply_schedule(policy, ops)


@pytest.mark.parametrize("policy", ["static", "lru", "hhzs"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_zone_accounting_property_deterministic(policy, seed):
    """Fallback for environments without hypothesis: fixed-seed
    schedules through the same invariant checker."""
    rng = np.random.default_rng(seed)
    ops = [(("submit", "step", "rotate", "release")[int(rng.integers(4))],
            int(rng.integers(0, 40))) for _ in range(120)]
    _apply_schedule(policy, ops)


# ======================================================================
# jax-gated: the real engine against dense references
# ======================================================================
@pytest.mark.skipif(not HAVE_JAX, reason="needs jax")
def test_gather_kv_matches_dense_reference():
    """`_gather_kv` must return exactly the tokens written, in order,
    before and after a tier migration."""
    from repro.serving import ServingEngine
    hbm, host = _pools(hbm=4, ps=4)
    mgr = HHZSKVManager(hbm, host, cache_zones=1)
    seq = mgr.on_prefill(0, tokens=13)
    ref = []
    for pos in range(13):
        pl = _payload(0, pos, SHAPE)
        mgr.pool_of(seq).write_token(mgr.writable_zone(seq), pl, pl)
        seq.length += 1
        ref.append(pl)
    eng = SimpleNamespace(
        mgr=mgr, page_size=hbm.page_size,
        cfg=SimpleNamespace(num_kv_heads=KV, head_dim_=D))
    req = SimpleNamespace(rid=0)
    k, v = ServingEngine._gather_kv(eng, req)
    for layer in range(L):
        want = np.stack([p[layer] for p in ref])
        np.testing.assert_array_equal(np.asarray(k[layer]), want)
        np.testing.assert_array_equal(np.asarray(v[layer]), want)
    mgr._seq_to_host(seq)               # migrate, then re-check
    k, _ = ServingEngine._gather_kv(eng, req)
    np.testing.assert_array_equal(np.asarray(k[0]),
                                  np.stack([p[0] for p in ref]))


@pytest.mark.skipif(not HAVE_JAX, reason="needs jax")
@pytest.mark.slow
def test_engine_matches_dense_decode_without_pressure():
    """With ample HBM the paged engine must generate the same tokens as
    the dense-cache decode path (bookkeeping correctness)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.models import model as M
    from repro.serving import Request, ServingEngine
    cfg = get_config("qwen3-1.7b").smoke()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.array([5, 9, 2, 7, 1, 3, 8, 4], np.int32)
    gen = 5

    eng = ServingEngine(cfg, params, hbm_zones=16, host_zones=16,
                        pages_per_zone=4, page_size=8, max_batch=1,
                        cache_zones=0)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=gen))
    eng.run(max_steps=20)
    got = eng.done[0].out_tokens

    toks = jnp.asarray(prompt)[None]
    logits = M.forward(cfg, params, {"tokens": toks}, remat=False)
    nxt = int(jnp.argmax(logits[0, -1]))
    ref = [nxt]
    clen = len(prompt)
    caches = M.init_caches(cfg, 1, 64)
    for t in range(len(prompt)):
        _, caches = M.decode_step(cfg, params, toks[:, t:t + 1],
                                  jnp.array([t], jnp.int32), caches)
    cur = nxt
    for i in range(gen - 1):
        lg, caches = M.decode_step(cfg, params,
                                   jnp.array([[cur]], jnp.int32),
                                   jnp.array([clen + i], jnp.int32), caches)
        cur = int(jnp.argmax(lg[0, -1]))
        ref.append(cur)
    assert got == ref


@pytest.mark.skipif(not HAVE_JAX, reason="needs jax")
@pytest.mark.slow
def test_engine_completes_under_pressure_with_migrations():
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import Request, ServingEngine
    cfg = get_config("qwen3-1.7b").smoke()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, hbm_zones=3, host_zones=48,
                        pages_per_zone=2, page_size=8, max_batch=4,
                        cache_zones=1)
    rng = np.random.default_rng(1)
    for i in range(6):
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
            max_new_tokens=4))
    stats = eng.run(max_steps=80)
    assert stats["done"] == 6
    assert stats["demotions"] + stats["host_placements"] > 0
    assert eng.hbm.num_free() >= 2
