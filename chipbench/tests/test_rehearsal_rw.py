"""The versioned store cell driven end to end on the CPU at a tiny size:
a sound run comes out correct with its write path in the window, a traced
run reads the write-path metrics, and a store that loses or reorders
versions underneath, or stamps two writes alike, comes out not correct."""
import json

import pytest

from chipbench import harness, run
from conftest import FAKE_PEAKS, tiny_scenario


def tiny_rw_cell(objects=2000):
    cell = harness.Cell(harness.load_benchmark(), "store-ycsb-a")
    sc = tiny_scenario()
    sc["lsm"]["store_values"] = True
    cell.config = dict(cell.config, objects=objects, scenario=sc)
    cell.traffic = dict(cell.traffic, rate_ops_per_virtual_s=200.0,
                        virtual_s_per_wall_s=2.0, warmup_wall_s=0.5,
                        readback_keys=2000, trace_seconds=1.0)
    return cell


def _run(cell, capsys, seed=2 ** 31 + 13, seconds=2.0, trace=False):
    rc = run.run_cell(cell, seed, seconds, trace, require_chip=False,
                      peaks=FAKE_PEAKS)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["info"]


def test_versioned_cell_is_correct_with_the_write_path_in_its_window(
        capsys):
    res, info = _run(tiny_rw_cell(), capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {
        "probe_mismatched_pairs", "window_without_device_probe",
        "stale_or_foreign_reads", "lost_updates",
        "reused_or_foreign_stamps", "window_without_write_path"}
    assert set(res["metrics"]) == {"store_ops_per_s", "setup_s"}
    assert info["flushes"] > 0 and info["compactions"] > 0
    assert info["reads_checked"] > 0 and info["puts_acked"] > 0
    assert info["readback_keys"] >= info["keys_written"] > 0


def test_versioned_traced_run_reads_the_write_path_metrics(capsys):
    res, _ = _run(tiny_rw_cell(), capsys, trace=True)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    assert m["store_write_amp"]["value"] > 0
    assert m["store_merge_ms_per_kupdate"]["value"] > 0
    assert m["store_probe_pairs_per_read"]["value"] > 0


def _drop_every_64th_insert(monkeypatch):
    """Every 64th put is acknowledged after its WAL record but never
    reaches the memtable."""
    from repro.lsm.tree import LSMTree
    real = LSMTree.put
    count = {"n": 0}

    def put(self, key, value=None, tombstone=False, tenant=None):
        count["n"] += 1
        if count["n"] % 64 or value is None or value & 0xFFFFFFFF == 0:
            yield from real(self, key, value, tombstone, tenant)
            return
        yield from self.backend.wal_append(self.cfg.obj_size)
        self.stats["puts_acked"] += 1

    monkeypatch.setattr(LSMTree, "put", put)


def _keep_older_version(monkeypatch):
    """Of the keys a compaction's inputs hold twice or more, every 64th
    keeps the older version."""
    from repro.lsm.tree import LSMTree
    real = LSMTree._merge_inputs

    def merge(self, level, inputs):
        keys, tombs, values, comp = real(self, level, inputs)
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


def _reuse_write_sequence(monkeypatch):
    """Every write after the load stores the same sequence, so a key's
    second write repeats its first one's stamp."""
    from repro.workloads import ycsb
    monkeypatch.setattr(ycsb.OpStream, "_value",
                        lambda self, key: ycsb.stamp(key, 1))


@pytest.mark.parametrize("fault,caught_by", [
    (_drop_every_64th_insert, ("lost_updates",)),
    (_keep_older_version, ("stale_or_foreign_reads", "lost_updates")),
    (_reuse_write_sequence, ("reused_or_foreign_stamps",)),
])
def test_versioned_fault_is_not_correct(fault, caught_by, monkeypatch,
                                        capsys):
    fault(monkeypatch)
    res, _ = _run(tiny_rw_cell(), capsys)
    assert res["correct"] is False
    assert sum(res["checks"][c]["value"] for c in caught_by) > 0, \
        res["checks"]
