"""Host spans of the store (``repro.obs.spans``) and the counters beside
them: the helper without jax, spans that never stay open across a DES
``yield``, one device call per read batch against one tree-wide filter
image, the re-probe after a membership change, the bytes handed to the
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
    """A loaded store whose background jobs have all finished, so its
    levels hold still until the next write."""
    from repro.workloads import run_load
    sc = tiny_scenario()
    sc = replace(sc, lsm=replace(sc.lsm, filter_impl=impl))
    db = DB("HHZS", sc, store_values=True)
    run_load(db, n_keys=keys)
    db.flush_all()
    db.drain()
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
    assert {"get_batch.levels", "level_index", "tree_image",
            "block_lookup", "probe",
            "probe.upload", "probe.pad", "probe.call", "probe.read", "hint",
            "migration.pick", "compaction.merge", "flush.merge",
            "sst.build", "filter.build"} <= names
    for name, _, outer in done:
        if name == "filter.build":
            assert outer[-1] == "sst.build"
        if name in ("probe.upload", "probe.pad", "probe.call",
                    "probe.read"):
            assert outer[-1] == "probe"
        if name in ("probe", "tree_image"):
            assert outer[-1] == "get_batch.levels"


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


def _expected_pairs(tree, batch):
    """For a batch of loaded keys, each held once: the first level that
    holds an SST, the (key, SST) pairs of every level (what the one probe
    gets), and the pairs of the levels the walk reaches for each key, down
    to the level that holds it."""
    where = {}
    for lvl, ssts in enumerate(tree.levels):
        for s in ssts:
            for k in batch:
                if s.min_key <= k <= s.max_key and s.find(k)[0]:
                    where.setdefault(k, lvl)
    first = min(l for l, ssts in enumerate(tree.levels) if ssts)
    covers = [(k, l) for k in batch for l, ssts in enumerate(tree.levels)
              for s in ssts if s.min_key <= k <= s.max_key]
    reached = [(k, l) for k, l in covers if l <= where[k]]
    return first, len(covers), len(reached)


@pytest.mark.parametrize("size", [1, 3, 8, 64])
def test_one_level_span_per_probed_level_and_one_of_each_per_call(
        recorded, size):
    """While the levels hold still, a read batch makes one device call
    against the tree's image, under one ``get_batch.levels`` span, and
    hands it the pairs of every level; ``filter_probes`` counts only the
    pairs of the levels the walk reached."""
    pytest.importorskip("jax")
    db, n = _jax_db()
    stats = db.tree.stats
    rng = np.random.default_rng(4 + size)
    for _ in range(2):
        batch = [int(k) for k in rng.choice(n, size, replace=False)]
        first, pairs, reached = _expected_pairs(db.tree, batch)
        before = dict(stats)
        recorded.clear()
        got = db.get_batch(batch)
        assert all(found for found, _ in got)
        done = _nest(recorded)
        assert [a["first"] for name, a, _ in done
                if name == "get_batch.levels"] == [first]
        assert stats["probe_calls"] - before["probe_calls"] == 1
        assert stats["probe_reprobes"] == before["probe_reprobes"]
        assert stats["probe_pairs"] - before["probe_pairs"] == pairs
        assert stats["filter_probes"] - before["filter_probes"] == reached
        for name in ("probe", "probe.pad", "probe.call", "probe.read"):
            assert sum(1 for m, _, _ in done if m == name) == 1
        uploads = [a["words"] for m, a, _ in done if m == "probe.upload"]
        assert (stats["probe_image_uploads"] - before["probe_image_uploads"]
                == len(uploads) <= 1)
        pads = [a["words"] for m, a, _ in done if m == "probe.pad"]
        assert all(w >= 1024 and w & (w - 1) == 0 for w in pads + uploads)


@pytest.mark.parametrize("impl", ["numpy", "jax"])
def test_filter_probes_count_the_pairs_of_the_levels_the_walk_reached(
        impl):
    """Every route hands the probe the pairs of all levels at once, and
    counts as ``filter_probes`` the pairs of the levels each key's walk
    reached, as the per-level probe did."""
    if impl == "jax":
        pytest.importorskip("jax")
    db, n = _jax_db(impl=impl)
    stats = db.tree.stats
    batch = list(range(0, n, 5))
    _, pairs, reached = _expected_pairs(db.tree, batch)
    assert reached < pairs
    before = dict(stats)
    db.get_batch(batch)
    assert stats["probe_pairs"] - before["probe_pairs"] == pairs
    assert stats["filter_probes"] - before["filter_probes"] == reached
    assert stats["probe_calls"] - before["probe_calls"] == (
        impl == "jax")


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
    """The tree's one image is uploaded on the first batch and serves every
    later batch while no SST is installed or removed."""
    pytest.importorskip("jax")
    from repro.lsm import filters
    db, n = _jax_db()
    stats = db.tree.stats
    rng = np.random.default_rng(8)
    for _ in range(5):
        db.get_batch([int(k) for k in rng.choice(n, 8, replace=False)])
    assert stats["probe_calls"] == 5
    assert stats["probe_image_uploads"] == 1
    image = db.tree._image[0]
    assert filters._resident[id(image)][0]() is image


def test_a_compaction_uploads_the_level_images_it_changed():
    """After compactions change the levels' membership, the old tree image
    leaves the device, and the next batch uploads the new one once; every
    answer matches the numpy route's, so no stale image answers."""
    pytest.importorskip("jax")
    from repro.lsm import filters
    dbs = [_jax_db(impl=impl)[0] for impl in ("jax", "numpy")]
    tree = dbs[0].tree
    batch = list(range(0, 1200, 25))
    first = [db.get_batch(batch) for db in dbs]
    assert first[0] == first[1]
    old = tree._image[0]
    assert id(old) in filters._resident
    for db in dbs:
        rng = np.random.default_rng(10)
        for i, k in enumerate(rng.integers(0, 1500, size=900)):
            db.put(int(k), b"w%d" % i)
        db.drain()
    assert tree.stats["compactions"] > 0
    # the old image leaves the device when the membership changes
    assert tree._image is None and id(old) not in filters._resident
    uploads0 = tree.stats["probe_image_uploads"]
    calls0 = tree.stats["probe_calls"]
    keys = list(range(0, 1600, 3))
    answers = [db.get_batch(keys) for db in dbs]
    assert answers[0] == answers[1]
    assert tree.stats["probe_calls"] - calls0 == 1
    assert tree.stats["probe_image_uploads"] - uploads0 == 1
    new = tree._image[0]
    assert new is not old and filters._resident[id(new)][0]() is new
    assert len(new) == sum(len(s.filter_words) for ssts in tree.levels
                           for s in ssts)


def _change_during_walk(db, how):
    """Make ``db``'s tree change its membership while ``get_batch`` yields
    in its first survivor's block lookup: a flush of fresh keys into L0,
    or a compaction-style rewrite of the deepest level's SSTs (same keys
    and versions, new SSTs).  Returns the rewritten SSTs, old and new."""
    tree = db.tree
    real = tree._probe_sst
    rewritten = []

    def change():
        if how == "flush":
            for k in range(5000, 5100):
                yield from tree.put(k, b"fresh%d" % k)
            yield from tree.flush_all()
            return
        deepest = max(l for l, ssts in enumerate(tree.levels) if ssts)
        for old in list(tree.levels[deepest]):
            new = tree._make_sst(old.keys, old.tombs, level=deepest,
                                 values=old.values)
            yield from tree.backend.write_sst(new, source="compaction")
            tree._remove_sst(old)
            tree.block_cache.drop_sst(old.sid)
            tree._install_sst(new, deepest)
            tree.backend.delete_sst(old)
            rewritten.append((old, new))
        tree.levels[deepest].sort(key=lambda s: s.min_key)

    def probe_sst(sst, key):
        if tree._probe_sst is probe_sst:
            tree._probe_sst = real
            yield from change()
        return (yield from real(sst, key))

    tree._probe_sst = probe_sst
    return rewritten


@pytest.mark.parametrize("how", ["flush", "rewrite"])
def test_a_membership_change_during_the_walk_probes_the_rest_again(how):
    """A flush or compaction that installs SSTs while the walk yields makes
    the next level's walk probe the remaining levels again, so each level
    walks the SSTs it holds when its walk starts; answers equal the numpy
    route's and per-key ``get``'s."""
    pytest.importorskip("jax")
    dbs = [_jax_db(impl=impl)[0] for impl in ("jax", "numpy")]
    levels = dbs[0].tree.levels
    shallow = min(l for l, ssts in enumerate(levels) if ssts)
    deep = max(l for l, ssts in enumerate(levels) if ssts)
    assert shallow < deep
    # a key the shallowest level holds comes first, so the walk yields
    # there with keys of the deepest level still pending
    batch = ([int(levels[shallow][0].keys[0])]
             + [int(s.keys[len(s.keys) // 2]) for s in levels[deep]])
    answers = []
    for db in dbs:
        stats = db.tree.stats
        before = dict(stats)
        rewritten = _change_during_walk(db, how)
        answers.append(db.get_batch(batch))
        reprobes = stats["probe_reprobes"] - before["probe_reprobes"]
        assert reprobes >= 1
        if db is dbs[0]:
            assert stats["probe_calls"] - before["probe_calls"] == (
                1 + reprobes)
        if how == "rewrite":
            # the deepest level's walk read the new SSTs, never the old
            assert rewritten
            assert all(old.num_reads == 0 for old, _ in rewritten)
            assert sum(new.num_reads for _, new in rewritten) >= len(
                levels[deep])
        db.drain()
        assert answers[-1] == [db.get(k) for k in batch]
    assert answers[0] == answers[1]
    assert all(found for found, _ in answers[0])


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
    assert {"hhzs:get_batch.levels", "hhzs:probe", "hhzs:probe.pad",
            "hhzs:probe.call", "hhzs:probe.read"} <= names
    assert all(lo <= a <= b <= hi for _, a, b in ours)
