"""Sweep driver: worker-count determinism, resume, selection, merging.

The driver's contract (``repro.workloads.sweep``): rows depend only on the
matrix spec — never on worker count, completion order, or what else sits
in the output file — and a rerun over an existing file skips completed
cells while preserving every foreign row byte-for-byte.
"""
import json
from pathlib import Path

import pytest

from repro.workloads import PoissonArrivals, ScenarioMatrix
from repro.workloads.sweep import (GridDBFactory, parse_cell_selector,
                                   run_sweep)

# tiny but real cells: ~1k keys loaded per cell, 20 virtual seconds of
# arrivals at a stable offered rate
FACTORY = GridDBFactory(key_div=512, load_div=4)


def tiny_matrix(schemes=("B3", "HHZS"), workloads=("A", "B")):
    return ScenarioMatrix(
        schemes=list(schemes), workloads=list(workloads),
        arrivals=[PoissonArrivals(50.0)], ssd_zone_budgets=[20],
        duration=20.0, warmup=5.0, key_div=512, seed=7,
        db_factory=FACTORY)


# ---------------------------------------------------------------------
def test_rows_identical_for_any_worker_count(tmp_path):
    """Same seed -> byte-identical output for 1 process vs a 2-worker pool."""
    out0 = tmp_path / "w0.json"
    out2 = tmp_path / "w2.json"
    rows0 = run_sweep(tiny_matrix(), out=out0, workers=0, verbose=False)
    rows2 = run_sweep(tiny_matrix(), out=out2, workers=2, verbose=False)
    assert rows0 == rows2
    assert out0.read_bytes() == out2.read_bytes()
    assert len(rows0) == 4 and [r["cell"] for r in rows0] == \
        [c.name for c in tiny_matrix().cells()]


def test_resume_skips_completed_cells(tmp_path):
    """Cells already in the output file are not re-run: a tampered value
    in a completed row survives the rerun, and only missing cells run."""
    out = tmp_path / "grid.json"
    m = tiny_matrix()
    first = [c.name for c in m.cells()][:2]
    run_sweep(m, out=out, workers=0, verbose=False, cells="0-1")
    rows = json.loads(out.read_text())
    assert [r["cell"] for r in rows] == first
    # tamper: if resume re-ran these cells the sentinel would be recomputed
    rows[0]["throughput"] = 123456.0
    out.write_text(json.dumps(rows, indent=1))
    final = run_sweep(tiny_matrix(), out=out, workers=0, verbose=False)
    assert len(final) == 4
    by_cell = {r["cell"]: r for r in final}
    assert by_cell[first[0]]["throughput"] == 123456.0
    # canonical order regardless of completion order
    assert [r["cell"] for r in final] == \
        [c.name for c in tiny_matrix().cells()]
    # fresh=False twice in a row: nothing to do, file unchanged
    before = out.read_bytes()
    run_sweep(tiny_matrix(), out=out, workers=0, verbose=False)
    assert out.read_bytes() == before


def test_fresh_rerun_keeps_unselected_and_unreached_rows(tmp_path):
    """resume=False re-runs selected cells but must never drop published
    rows for cells it was not asked to (or did not get to) re-run."""
    out = tmp_path / "grid.json"
    m = tiny_matrix()
    names = [c.name for c in m.cells()]
    run_sweep(m, out=out, workers=0, verbose=False)          # all 4 cells
    rows = json.loads(out.read_text())
    for r in rows:
        r["throughput"] = 7777.0                              # sentinel
    out.write_text(json.dumps(rows, indent=1))
    # fresh re-run of cell 0 only: cell 0 recomputed, others untouched
    final = run_sweep(tiny_matrix(), out=out, workers=0, verbose=False,
                      resume=False, cells="0")
    by_cell = {r["cell"]: r for r in final}
    assert by_cell[names[0]]["throughput"] != 7777.0
    assert all(by_cell[n]["throughput"] == 7777.0 for n in names[1:])
    # fresh run with a zero budget: nothing recomputed, nothing lost
    final = run_sweep(tiny_matrix(), out=out, workers=0, verbose=False,
                      resume=False, budget_s=0.0)
    assert len(final) == 4 and {r["cell"] for r in final} == set(names)


def test_foreign_rows_preserved(tmp_path):
    """Rows whose cell is not part of the running matrix (other sweeps,
    tenant/fault rows) survive untouched — merge-never-overwrite."""
    out = tmp_path / "grid.json"
    foreign = [{"cell": "X/other/sweep/z9", "tenant": "steady",
                "marker": "do-not-touch"}]
    out.write_text(json.dumps(foreign, indent=1))
    rows = run_sweep(tiny_matrix(schemes=("B3",), workloads=("A",)),
                     out=out, workers=0, verbose=False)
    final = json.loads(out.read_text())
    assert final[0] == foreign[0]          # foreign rows first, untouched
    assert len(final) == 1 + len(rows)


def test_budget_stops_dispatch(tmp_path):
    """budget_s=0: nothing is dispatched; completed rows are kept."""
    out = tmp_path / "grid.json"
    rows = run_sweep(tiny_matrix(), out=out, workers=0, verbose=False,
                     budget_s=0.0)
    assert rows == [] and json.loads(out.read_text()) == []


def test_cell_selector():
    sel = parse_cell_selector("0,2-3")
    assert [i for i in range(5) if sel(i, "x")] == [0, 2, 3]
    sel = parse_cell_selector("HHZS/*/z20")
    assert sel(0, "HHZS/A/poisson(50)/z20")
    assert not sel(0, "B3/A/poisson(50)/z20")
    sel = parse_cell_selector(None)
    assert sel(17, "anything")


def test_duplicate_cell_names_rejected(tmp_path):
    m = tiny_matrix(schemes=("B3", "B3"), workloads=("A",))
    with pytest.raises(ValueError, match="duplicate cell names"):
        run_sweep(m, out=tmp_path / "g.json", workers=0, verbose=False)


def test_validate_hook_gates_writes(tmp_path):
    """A failing validate callback aborts before anything is written."""
    out = tmp_path / "grid.json"

    def reject(rows):
        raise ValueError("schema says no")

    with pytest.raises(ValueError, match="schema says no"):
        run_sweep(tiny_matrix(schemes=("B3",), workloads=("A",)),
                  out=out, workers=0, verbose=False, validate=reject)
    assert not out.exists()


def device_probe_db(scheme, ssd_zones):
    """A caller's own store factory, outside the sweep module, whose
    stores probe Bloom filters on the JAX device."""
    from dataclasses import replace
    from repro.lsm import DB, ScenarioConfig
    from repro.workloads.ycsb import run_load
    sc = ScenarioConfig(ssd_zones=ssd_zones)
    db = DB(scheme, replace(sc, lsm=replace(sc.lsm, filter_impl="jax")))
    db.n_keys = sc.paper_keys // 2048
    run_load(db, n_keys=db.n_keys)
    db.flush_all()
    return db


def test_device_probe_cells_refuse_worker_pools(tmp_path):
    """A store that probes on the JAX device holds the device: a pool
    worker refuses it, whatever factory built it, before it serves an
    op; the same cell runs in the sweep's own process."""
    pytest.importorskip("jax")
    matrix = tiny_matrix(schemes=("HHZS",), workloads=("C",))
    matrix.db_factory = device_probe_db
    out = tmp_path / "dev.json"
    with pytest.raises(ValueError, match="workers=0"):
        run_sweep(matrix, out=out, workers=2, verbose=False)
    assert not out.exists()
    rows = run_sweep(matrix, workers=0, verbose=False)
    assert len(rows) == 1 and rows[0]["op_counts"]["read"] > 0
    # the numpy-probe factory still shards over workers
    assert run_sweep(tiny_matrix(schemes=("HHZS",), workloads=("C",)),
                     workers=1, verbose=False)
