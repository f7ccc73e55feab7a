"""Golden runs of the open-loop engine behind its three front ends.

``run_open_loop``, ``run_multi_tenant`` and ``run_drift`` share one engine
(``repro.workloads.runner._serve``).  Each case below drives one feature
of it on the tiny scenario — batched reads, the hard time limit, stall
windows, whole-store and per-shard crashes, admission holds, the feedback
control plane, tenant departure with phase marks — and compares every
result row's ``to_json()`` (key order included) and the number of events
the simulator scheduled with the values recorded in
``tests/data/open_loop_golden.json``.  A change to the engine that moves a
single event, seed or row field fails here.
"""
import json
from pathlib import Path

import pytest

from conftest import tiny_scenario
from repro.cluster import ShardedDB
from repro.core.middleware import AdmissionConfig
from repro.lsm import DB
from repro.workloads import (DriftTenant, Phase, PoissonArrivals,
                             TenantSpec, TraceProgram, WorkloadSpec, YCSB,
                             run_drift, run_load, run_multi_tenant,
                             run_open_loop)
from repro.zoned.faults import FaultSpec, StallWindow

GOLDEN = Path(__file__).parent / "data" / "open_loop_golden.json"


def _loaded(scheme="HHZS", n=1000):
    db = DB(scheme, tiny_scenario(), store_values=True)
    run_load(db, n_keys=n)
    db.flush_all()
    return db, n


def _batched_reads():
    db, n = _loaded()
    return db, lambda: [run_open_loop(
        db, YCSB["B"], PoissonArrivals(60.0), duration=20.0, n_keys=n,
        warmup=2.0, max_concurrency=2, seed=3, read_batch=8).to_json()]


def _hard_time_limit():
    db, n = _loaded("B3")
    return db, lambda: [run_open_loop(
        db, YCSB["A"], PoissonArrivals(30.0), duration=15.0, n_keys=n,
        warmup=2.0, max_concurrency=2, seed=5, drain=False).to_json()]


def _stall_and_crash():
    db, n = _loaded("B3")
    # two arrivals fall in the outage and are refused
    spec = FaultSpec(name="stall+crash", crash_at=20.0,
                     stalls=(StallWindow(at=5.0, duration=4.0,
                                         device="both"),),
                     recovery_slo_s=5.0)
    return db, lambda: [run_open_loop(
        db, YCSB["A"], PoissonArrivals(15.0), duration=40.0, n_keys=n,
        warmup=2.0, max_concurrency=4, seed=13, faults=spec).to_json()]


def _shard_crash():
    n = 600
    db = ShardedDB("B3", tiny_scenario(), shards=2, routing="range",
                   key_space=n, store_values=True)
    run_load(db, n_keys=n)
    db.flush_all()
    # the crash catches one op in flight on shard 1
    spec = FaultSpec(name="crash-s1", crash_at=10.37, crash_shard=1,
                     recovery_slo_s=5.0)
    return db, lambda: [run_open_loop(
        db, YCSB["A"], PoissonArrivals(80.0), duration=20.0, n_keys=n,
        warmup=2.0, max_concurrency=4, seed=11, faults=spec).to_json()]


def _multi_tenant_rows(res):
    return res.rows() + [{"n_arrived": res.n_arrived,
                          "n_completed": res.n_completed,
                          "max_queue_depth": res.max_queue_depth,
                          "policy": res.policy}]


def _tenants(slo_p99=None):
    return [TenantSpec("steady", YCSB["A"], PoissonArrivals(6.0),
                       protected=True, slo_p99=slo_p99),
            TenantSpec("crowd", YCSB["B"], PoissonArrivals(50.0))]


def _multi_tenant_delay():
    db, n = _loaded()
    policy = AdmissionConfig(policy="delay", queue_threshold=3,
                             poll_interval=0.2)
    return db, lambda: _multi_tenant_rows(run_multi_tenant(
        db, _tenants(), duration=20.0, n_keys=n, warmup=2.0,
        max_concurrency=2, seed=13, policy=policy))


def _multi_tenant_feedback_crash():
    db, n = _loaded()
    policy = AdmissionConfig(policy="feedback", feedback_interval=1.0)
    spec = FaultSpec(name="crash", crash_at=12.0)
    return db, lambda: _multi_tenant_rows(run_multi_tenant(
        db, _tenants(slo_p99=0.05), duration=25.0, n_keys=n, warmup=2.0,
        max_concurrency=3, seed=17, policy=policy, faults=spec))


def _drift_departure_telemetry():
    db, n = _loaded("B3")
    db.enable_telemetry(2.0)
    mix = WorkloadSpec("mix", read=0.5, update=0.5, alpha=0.9)
    readmix = WorkloadSpec("readmix", read=0.9, update=0.1, alpha=0.99)
    prog = TraceProgram(
        "churn-golden",
        # batch departs with ops queued, then comes back
        (Phase("both", 10.0, mix, tenants=("base", "batch")),
         Phase("solo", 10.0, readmix, tenants=("base",), scan_burst=0.1,
               scan_len=20),
         Phase("again", 5.0, mix, tenants=("base", "batch"))),
        (DriftTenant("base", PoissonArrivals(10.0)),
         DriftTenant("batch", PoissonArrivals(60.0))),
        drain_s=30.0)

    def run():
        rows = [r.to_json() for r in run_drift(db, prog, n_keys=n,
                                               warmup=1.0,
                                               max_concurrency=2, seed=19)]
        marks = db.metrics.timeline(meta={}).get("marks", [])
        return rows + [{"marks": [m["label"] for m in marks]}]
    return db, run


CASES = {
    "open_loop_read_batch": _batched_reads,
    "open_loop_hard_time_limit": _hard_time_limit,
    "open_loop_stall_and_crash": _stall_and_crash,
    "open_loop_shard_crash": _shard_crash,
    "multi_tenant_delay": _multi_tenant_delay,
    "multi_tenant_feedback_crash": _multi_tenant_feedback_crash,
    "drift_departure_telemetry": _drift_departure_telemetry,
}


def observe(case: str) -> dict:
    """Run one case; return its rows, scheduled-event count and clock."""
    db, run = CASES[case]()
    sim = db.sim
    sched0, now0 = sim.scheduled, sim.now
    rows = run()
    return {"rows": rows, "scheduled": sim.scheduled - sched0,
            "elapsed": sim.now - now0}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_reproduces_the_recorded_run(case):
    want = json.loads(GOLDEN.read_text())[case]
    got = json.loads(json.dumps(observe(case)))
    assert got["scheduled"] == want["scheduled"]
    assert got["elapsed"] == want["elapsed"]
    # row by row, in key order, so a diff names the first row that moved
    assert len(got["rows"]) == len(want["rows"])
    for g, w in zip(got["rows"], want["rows"]):
        assert json.dumps(g) == json.dumps(w)
