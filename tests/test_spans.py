"""Host spans of the store (``repro.obs.spans``) and the counters beside
them: the helper without jax, spans that never stay open across a DES
``yield``, one span per level and per device call, the bytes handed to the
device, the kernel's schedule count, and the spans in a real profiler
trace."""
import contextlib
import glob
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from conftest import tiny_scenario
from repro.lsm import DB
from repro.obs import spans
from repro.zoned.sim import Sim

SRC = Path(__file__).resolve().parents[1] / "src"


def test_span_is_one_shared_null_context_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "import contextlib\n"
            "from repro.obs import spans\n"
            "a, b = spans.span('x'), spans.span('y', level=3)\n"
            "assert a is b and isinstance(a, contextlib.nullcontext)\n"
            "with a:\n"
            "    pass\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={"PYTHONPATH": str(SRC)})


def test_span_is_a_trace_annotation_once_jax_is_loaded(monkeypatch):
    """Once jax is loaded a span is a ``TraceAnnotation`` while the
    profiler records, and the shared null context while it does not."""
    jax = pytest.importorskip("jax")
    assert not jax.profiler.TraceAnnotation.is_enabled()
    off = spans.span("probe.pad", words=1024)
    assert off is spans.span("put") and isinstance(off, contextlib.nullcontext)
    monkeypatch.setattr(spans, "_recording", lambda: True)
    s = spans.span("probe.pad", words=1024)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


class _Recorded:
    """Stands in for ``TraceAnnotation``: logs every open and close."""

    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name[len(spans.PREFIX):], args

    def __enter__(self):
        self.log.append(("open", self))

    def __exit__(self, *exc):
        self.log.append(("close", self))


@pytest.fixture
def recorded(monkeypatch):
    log = []
    monkeypatch.setattr(_Recorded, "log", log)
    monkeypatch.setattr(spans, "_annotation", _Recorded)
    monkeypatch.setattr(spans, "_recording", lambda: True)
    return log


def _nest(log):
    """Replays the log, requiring strict LIFO order; returns each closed
    span as (name, args, names of its enclosing spans, outermost first)."""
    stack, done = [], []
    for what, s in log:
        if what == "open":
            stack.append(s)
            continue
        assert stack and stack[-1] is s, (
            f"{s.name} closed while {[t.name for t in stack]} were open")
        stack.pop()
        done.append((s.name, s.args, tuple(t.name for t in stack)))
    assert not stack, [t.name for t in stack]
    return done


def _jax_db(keys=1200, impl="jax"):
    from repro.workloads import run_load
    sc = tiny_scenario()
    sc = replace(sc, lsm=replace(sc.lsm, filter_impl=impl))
    db = DB("HHZS", sc, store_values=True)
    run_load(db, n_keys=keys)
    db.flush_all()
    return db, keys


def test_spans_close_in_lifo_order_under_concurrent_reads_and_compaction(
        recorded):
    pytest.importorskip("jax")
    from repro.workloads import YCSB, PoissonArrivals, run_open_loop
    db, n = _jax_db()
    before = dict(db.tree.stats)
    recorded.clear()
    res = run_open_loop(db, YCSB["A"], PoissonArrivals(40.0), duration=20.0,
                        n_keys=n, read_batch=8, max_concurrency=6, seed=3)
    done = _nest(recorded)
    assert res.n_measured > 300
    assert db.tree.stats["compactions"] > before["compactions"]
    names = {name for name, _, _ in done}
    assert {"get_batch.level", "level_index", "block_lookup", "probe",
            "probe.upload", "probe.pad", "probe.call", "probe.read", "hint",
            "migration.pick", "compaction.merge", "flush.merge",
            "sst.build", "filter.build"} <= names
    for name, _, outer in done:
        if name == "filter.build":
            assert outer[-1] == "sst.build"
        if name in ("probe.upload", "probe.pad", "probe.call",
                    "probe.read"):
            assert outer[-1] == "probe"
        if name == "probe":
            assert outer[-1] == "get_batch.level"


def test_write_path_spans_and_byte_counters(recorded):
    """The put, the WAL append, the memtable rotation, the placement of a
    new SST and a migration's moves each open a span, in LIFO order; the
    middleware counts each write stream's bytes under its device."""
    from repro.workloads import YCSB, PoissonArrivals, run_open_loop
    db, n = _jax_db(keys=3000, impl="numpy")
    be, tree = db.backend, db.tree
    before, acked0 = dict(be.stats), tree.stats["puts_acked"]
    recorded.clear()
    run_open_loop(db, YCSB["A"], PoissonArrivals(60.0), duration=40.0,
                  n_keys=n, read_batch=8, max_concurrency=16, seed=3)
    done = _nest(recorded)
    names = {name for name, _, _ in done}
    assert {"put", "wal.append", "memtable.rotate", "placement",
            "migration.move"} <= names
    for name, _, outer in done:
        # none wakes another process, so none holds another span
        if name in ("put", "wal.append", "placement", "migration.move"):
            assert not outer, (name, outer)
        if name == "memtable.rotate":
            assert outer in ((), ("put",))
    moved = {k: be.stats[k] - before[k] for k in before
             if k.startswith("write_bytes.")}
    assert all(v > 0 for v in moved.values()), moved
    assert (moved["write_bytes.ssd"] + moved["write_bytes.hdd"]
            == sum(moved["write_bytes." + s]
                   for s in ("wal", "flush", "compaction", "migration")))
    assert tree.stats["puts_acked"] > acked0


def _expected_levels(tree, batch):
    """Levels ``get_batch`` visits for a batch of loaded keys, each held
    once, and the levels among them where some pending key has a
    candidate SST (one device call each)."""
    where = {}
    for lvl, ssts in enumerate(tree.levels):
        for s in ssts:
            for k in batch:
                if s.min_key <= k <= s.max_key and s.find(k)[0]:
                    where.setdefault(k, lvl)
    deepest = max(where[k] for k in batch)
    visited = [l for l in range(deepest + 1) if tree.levels[l]]
    called = [l for l in visited
              if any(s.min_key <= k <= s.max_key for s in tree.levels[l]
                     for k in batch if where[k] >= l)]
    return visited, called


def test_one_level_span_per_probed_level_and_one_of_each_per_call(
        recorded):
    pytest.importorskip("jax")
    db, n = _jax_db()
    rng = np.random.default_rng(4)
    for size in (1, 3, 8):
        batch = [int(k) for k in rng.choice(n, size, replace=False)]
        visited, called = _expected_levels(db.tree, batch)
        calls0 = db.tree.stats["probe_calls"]
        uploads0 = db.tree.stats["probe_image_uploads"]
        recorded.clear()
        got = db.get_batch(batch)
        assert all(found for found, _ in got)
        done = _nest(recorded)
        levels = [a["level"] for name, a, _ in done
                  if name == "get_batch.level"]
        assert levels == visited
        assert db.tree.stats["probe_calls"] - calls0 == len(called)
        for name in ("probe", "probe.pad", "probe.call", "probe.read"):
            assert sum(1 for m, _, _ in done if m == name) == len(called)
        uploads = [a["words"] for m, a, _ in done if m == "probe.upload"]
        assert (db.tree.stats["probe_image_uploads"] - uploads0
                == len(uploads) <= len(called))
        pads = [a["words"] for m, a, _ in done if m == "probe.pad"]
        assert all(w >= 1024 and w & (w - 1) == 0 for w in pads + uploads)


def test_probe_h2d_bytes_are_the_padded_arrays_the_device_got(monkeypatch):
    """Each call hands over its host arrays (the padded pairs); a level's
    image crosses once, when it is uploaded, and then stays."""
    jax = pytest.importorskip("jax")
    from repro.kernels.bloom_probe import ops
    db, n = _jax_db()
    handed, uploaded = [], []
    real, real_put = ops.probe_pairs, jax.device_put

    def probe_pairs(*arrays, k_hashes):
        handed.append(sum(a.nbytes for a in arrays
                          if isinstance(a, np.ndarray)))
        return real(*arrays, k_hashes=k_hashes)

    def device_put(x, *args, **kw):
        uploaded.append(np.asarray(x).nbytes)
        return real_put(x, *args, **kw)

    monkeypatch.setattr(ops, "probe_pairs", probe_pairs)
    monkeypatch.setattr(jax, "device_put", device_put)
    stats = db.tree.stats
    b0, c0 = stats["probe_h2d_bytes"], stats["probe_calls"]
    rng = np.random.default_rng(6)
    for _ in range(6):
        db.get_batch([int(k) for k in rng.choice(n, 8, replace=False)])
    assert handed and stats["probe_calls"] - c0 == len(handed)
    assert 0 < len(uploaded) < len(handed)
    assert all(b == 4 * 256 * 4 for b in handed)
    assert stats["probe_h2d_bytes"] - b0 == sum(handed) + sum(uploaded)


def test_each_level_image_is_uploaded_once_while_its_membership_holds():
    pytest.importorskip("jax")
    db, n = _jax_db()
    stats = db.tree.stats
    rng = np.random.default_rng(8)
    probed = set()
    for _ in range(5):
        batch = [int(k) for k in rng.choice(n, 8, replace=False)]
        probed.update(_expected_levels(db.tree, batch)[1])
        db.get_batch(batch)
    assert stats["probe_calls"] > len(probed)
    assert stats["probe_image_uploads"] == len(probed)


def test_a_compaction_uploads_the_level_images_it_changed():
    """After compactions change levels' membership, the next probe of each
    changed level uploads its new image and frees the old one's device
    copy; every answer matches the numpy route's, so no stale image
    answers."""
    pytest.importorskip("jax")
    from repro.lsm import filters
    dbs = [_jax_db(impl=impl)[0] for impl in ("jax", "numpy")]
    tree = dbs[0].tree
    batch = list(range(0, 1200, 25))
    first = [db.get_batch(batch) for db in dbs]
    assert first[0] == first[1]
    old = {lvl: idx[4] for lvl, idx in tree._ridx.items()
           if idx[4] is not None and len(idx[4])}
    assert all(id(img) in filters._resident for img in old.values())
    members = [list(ssts) for ssts in tree.levels]
    for db in dbs:
        rng = np.random.default_rng(10)
        for i, k in enumerate(rng.integers(0, 1500, size=900)):
            db.put(int(k), b"w%d" % i)
        db.drain()
    assert dbs[0].tree.stats["compactions"] > 0
    moved = [l for l in old if tree.levels[l] != members[l]]
    assert moved
    # a changed level's old image leaves the device when the level changes
    assert all(id(old[l]) not in filters._resident for l in moved)
    uploads0 = tree.stats["probe_image_uploads"]
    keys = list(range(0, 1600, 3))
    answers = [db.get_batch(keys) for db in dbs]
    assert answers[0] == answers[1]
    assert tree.stats["probe_image_uploads"] - uploads0 >= len(
        [l for l in moved if tree.levels[l]])
    for lvl in moved:
        if tree.levels[lvl]:
            new = tree._ridx[lvl][4]
            assert new is not old[lvl]
            assert filters._resident[id(new)][0]() is new


def test_numpy_route_counts_no_device_calls():
    from repro.workloads import run_load
    db = DB("HHZS", tiny_scenario(), store_values=True)
    run_load(db, n_keys=600)
    db.flush_all()
    db.get_batch(list(range(0, 600, 7)))
    assert db.tree.stats["filter_probes"] > 0
    assert db.tree.stats["probe_calls"] == 0
    assert db.tree.stats["probe_h2d_bytes"] == 0


@pytest.mark.parametrize("pairs,words,want", [
    (1, 1, 16 * 256 + 4 * 1024),
    (256, 1024, 16 * 256 + 4 * 1024),
    (257, 1025, 16 * 512 + 4 * 2048),
    (3000, 655360, 16 * 4096 + 4 * 2 ** 20),
])
def test_padded_bytes(pairs, words, want):
    from repro.lsm import filters
    assert filters.padded_bytes(pairs, words) == want


def test_sim_scheduled_counts_every_scheduled_entry():
    sim = Sim()
    assert sim.scheduled == 0
    sim.timeout(1.0)
    sim.timeout(1.0, daemon=True)
    sim.schedule_at(2.0)
    sim.schedule_many([0.5, 0.7, 0.6])
    assert sim.scheduled == 6

    def proc():
        yield 0.25                      # a bare delay: one resume entry
        yield sim.timeout(0.25)

    sim.process(proc())                 # the start: one entry
    q = sim.monotone_queue()
    q.schedule_at(3.0)
    q.complete_at(3.5)
    assert sim.scheduled == 9
    sim.run()
    assert sim.scheduled == 11


def test_spans_reach_the_profiler_trace_inside_an_outer_annotation(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData, TraceAnnotation
    db, n = _jax_db(keys=600)
    db.get_batch([1, 2, 3])                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("outer"):
            db.get_batch([5, 77, 301, 599])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "outer" or ev.name.startswith(spans.PREFIX)]
    (_, lo, hi), = [h for h in host if h[0] == "outer"]
    ours = [h for h in host if h[0] != "outer"]
    names = {h[0] for h in ours}
    assert {"hhzs:get_batch.level", "hhzs:probe", "hhzs:probe.pad",
            "hhzs:probe.call", "hhzs:probe.read"} <= names
    assert all(lo <= a <= b <= hi for _, a, b in ours)
