"""Real Bloom-filter stack: cross-implementation differential + FP bounds.

The hash family is unified across three implementations — the pure-numpy
fallback (``repro.lsm.filters``), the jnp oracle
(``repro.kernels.bloom_probe.ref``) and the Pallas kernel (interpret
mode) — all fed by the same host-side splitmix64 pre-hash.  They must
agree bit-for-bit on hit masks, including on adversarial key sets
(duplicates, 0, 2**64 - 1).
"""
import math

import numpy as np
import pytest

from repro.lsm import filters


def _adversarial_keys(rng, n):
    keys = rng.integers(0, 2**63, n).astype(np.uint64)
    keys[0] = np.uint64(0)
    keys[1] = np.uint64(2**64 - 1)
    keys[2] = np.uint64(2**64 - 1)          # duplicate extreme
    keys[3:6] = keys[6]                     # duplicate run
    return keys


# ----------------------------------------------------------------------
@pytest.mark.parametrize("bits_per_key,n", [(10, 1024), (4, 2048), (16, 512)])
def test_numpy_build_probe_no_false_negatives(bits_per_key, n):
    rng = np.random.default_rng(0)
    keys = _adversarial_keys(rng, n)
    nw, k = filters.filter_params(n, bits_per_key)
    lo, hi = filters.split_hash(keys)
    bits = filters.build_filter_np(lo, hi, nw, k)
    assert filters.probe_np(lo, hi, bits, k).all(), \
        "a Bloom filter must never produce false negatives"


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("bits_per_key", [4, 10, 16])
def test_build_filter_fp_rate_within_tolerance(seed, bits_per_key):
    """Measured FP rate tracks the theoretical (1 - e^{-kn/m})^k."""
    rng = np.random.default_rng(seed)
    n = 4096
    member = rng.integers(0, 2**62, n).astype(np.uint64)
    nw, k = filters.filter_params(n, bits_per_key)
    lo, hi = filters.split_hash(member)
    bits = filters.build_filter_np(lo, hi, nw, k)
    # disjoint non-member population
    non = rng.integers(2**62, 2**63, 20_000).astype(np.uint64)
    qlo, qhi = filters.split_hash(non)
    fp = float(filters.probe_np(qlo, qhi, bits, k).mean())
    theory = (1.0 - math.exp(-k * n / (nw * 32.0))) ** k
    assert theory * 0.5 <= fp <= theory * 2.0 + 1e-4, (fp, theory)


def test_scalar_probe_matches_vectorized():
    """The per-key `get` fast path (python ints) is bitwise-identical to
    the vectorized numpy probe."""
    rng = np.random.default_rng(7)
    member = _adversarial_keys(rng, 512)
    nw, k = filters.filter_params(len(member), 10)
    lo, hi = filters.split_hash(member)
    bits = filters.build_filter_np(lo, hi, nw, k)
    queries = np.concatenate([member[:256],
                              rng.integers(0, 2**64, 1024, dtype=np.uint64)])
    qlo, qhi = filters.split_hash(queries)
    vec = filters.probe_np(qlo, qhi, bits, k)
    sca = np.array([filters.probe_one_np(int(q), bits, k) for q in queries])
    assert (vec == sca).all()


def test_pairs_probe_matches_single_filter():
    """The ragged (key x filter) pairs probe equals per-filter probes."""
    rng = np.random.default_rng(11)
    sets = [rng.integers(0, 2**63, n).astype(np.uint64)
            for n in (64, 300, 1000)]
    built = []
    for keys in sets:
        nw, k = filters.filter_params(len(keys), 10)
        lo, hi = filters.split_hash(keys)
        built.append((filters.build_filter_np(lo, hi, nw, k), nw, k))
    k = built[0][2]
    queries = rng.integers(0, 2**64, 512, dtype=np.uint64)
    qlo, qhi = filters.split_hash(queries)
    # pairs: every query against every filter
    bits_concat = np.concatenate([b for b, _, _ in built])
    offs, cur = [], 0
    for _, nw, _ in built:
        offs.append(cur)
        cur += nw
    p_lo = np.tile(qlo, len(built))
    p_hi = np.tile(qhi, len(built))
    p_off = np.repeat(np.array(offs, np.int64), len(queries))
    p_nw = np.repeat(np.array([nw for _, nw, _ in built], np.int64),
                     len(queries))
    pairs = filters.probe_pairs_np(p_lo, p_hi, p_off, p_nw, bits_concat, k)
    singles = np.concatenate([filters.probe_np(qlo, qhi, b, k)
                              for b, _, _ in built])
    assert (pairs == singles).all()


# ----------------------------------------------------------------------
def test_numpy_vs_jnp_vs_pallas_bit_identical():
    """All three implementations agree exactly on hit masks (adversarial
    keys: duplicates, 0, 2**64-1).  Skip-guarded: the no-jax tier-1 leg
    still exercises every numpy test above."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels.bloom_probe.ops import probe
    from repro.kernels.bloom_probe.ref import (build_filter,
                                               bloom_probe_pairs_ref,
                                               bloom_probe_ref)

    rng = np.random.default_rng(3)
    member = _adversarial_keys(rng, 4096)
    nw, k = filters.filter_params(len(member), 10)
    lo, hi = filters.split_hash(member)
    bits_np = filters.build_filter_np(lo, hi, nw, k)
    bits_j = np.asarray(build_filter(jnp.array(lo), jnp.array(hi), nw,
                                     k_hashes=k))
    assert (bits_np == bits_j).all(), "builders diverge"

    queries = np.concatenate([
        member[:1024],
        np.array([0, 2**64 - 1, 2**64 - 1, 1], dtype=np.uint64),
        rng.integers(0, 2**64, 1020, dtype=np.uint64)])
    qlo, qhi = filters.split_hash(queries)
    h_np = filters.probe_np(qlo, qhi, bits_np, k)
    h_ref = np.asarray(bloom_probe_ref(jnp.array(qlo), jnp.array(qhi),
                                       jnp.array(bits_np),
                                       k_hashes=k)).astype(bool)
    h_ker = np.asarray(probe(jnp.array(qlo), jnp.array(qhi),
                             jnp.array(bits_np), k_hashes=k,
                             interpret=True)).astype(bool)
    assert (h_np == h_ref).all(), "numpy fallback != jnp oracle"
    assert (h_np == h_ker).all(), "numpy fallback != pallas kernel"
    assert h_np[:1024].all(), "false negative"

    # ragged pairs probe: jnp route == numpy route
    off = np.zeros(len(queries), np.int64)
    nws = np.full(len(queries), nw, np.int64)
    p_ref = np.asarray(bloom_probe_pairs_ref(
        jnp.array(qlo), jnp.array(qhi), jnp.array(off.astype(np.int32)),
        jnp.array(nws.astype(np.uint32)), jnp.array(bits_np),
        k_hashes=k)).astype(bool)
    assert (p_ref == h_np).all()


def test_tree_jax_impl_matches_numpy_impl():
    """A store probing through the kernel package returns identical
    results to the numpy-fallback store (filter_impl is I/O-invisible)."""
    pytest.importorskip("jax")
    from dataclasses import replace

    from conftest import tiny_scenario
    from repro.lsm import DB

    answers = []
    for impl in ("numpy", "jax"):
        sc = tiny_scenario()
        sc = replace(sc, lsm=replace(sc.lsm, filter_impl=impl))
        db = DB("HHZS", sc, store_values=True)
        rng = np.random.default_rng(5)
        model = {}
        for i, k in enumerate(rng.integers(0, 200, size=400)):
            v = b"v%d-%d" % (k, i)
            db.put(int(k), v)
            model[int(k)] = v
        db.drain()
        keys = list(range(0, 250))
        answers.append(db.get_batch(keys))
        for key, got in zip(keys, answers[-1]):
            assert got == (key in model, model.get(key))
    assert answers[0] == answers[1]


# ----------------------------------------------------------------------
def _ragged_case(n_pairs, total_words, seed=0):
    """Two packed filters filling ``total_words`` and ``n_pairs`` probes
    spread over both (members and non-members)."""
    rng = np.random.default_rng(seed)
    w1 = max(1, total_words // 3)
    sizes = [w1, total_words - w1] if total_words > 1 else [1]
    bits, offs, members = [], [], []
    for i, nw in enumerate(sizes):
        keys = rng.integers(0, 2**63, nw * 3).astype(np.uint64)
        lo, hi = filters.split_hash(keys)
        bits.append(filters.build_filter_np(lo, hi, nw, 7))
        offs.append(sum(sizes[:i]))
        members.append(keys)
    which = rng.integers(0, len(sizes), n_pairs)
    keys = np.array([members[w][rng.integers(len(members[w]))]
                     if rng.random() < 0.5 else rng.integers(2**63, 2**64,
                                                             dtype=np.uint64)
                     for w in which], dtype=np.uint64)
    lo, hi = filters.split_hash(keys) if n_pairs else \
        (np.zeros(0, np.uint32), np.zeros(0, np.uint32))
    off = np.array([offs[w] for w in which], np.int64)
    nw = np.array([sizes[w] for w in which], np.int64)
    return lo, hi, off, nw, np.concatenate(bits)


_EDGES = [
    # pair count at the bucket edges (floor 256), filter image fixed
    (0, 1500), (1, 1500), (255, 1500), (256, 1500), (257, 1500),
    (511, 1500), (512, 1500), (513, 1500),
    # filter image at the bucket edges (floor 1024), pair count fixed
    (300, 1), (300, 1023), (300, 1024), (300, 1025), (300, 2048),
    (300, 2049),
]


@pytest.mark.parametrize("n_pairs,total_words", _EDGES)
def test_device_probe_padding_bit_identical(n_pairs, total_words):
    """The jitted probe pads pairs and words to power-of-two buckets;
    padding never changes an answer and is sliced off."""
    pytest.importorskip("jax")
    lo, hi, off, nw, bits = _ragged_case(n_pairs, total_words)
    assert len(bits) == total_words
    out = filters.probe_pairs_device(lo, hi, off, nw, bits, 7)
    assert out.shape == (filters.bucket(n_pairs, filters.MIN_PAIRS_BUCKET),)
    got = filters.probe_pairs(lo, hi, off, nw, bits, 7, impl="jax")
    want = filters.probe_pairs_np(lo, hi, off, nw, bits, 7)
    assert got.shape == (n_pairs,) and got.dtype == bool
    assert (got == want).all()


def _uploads(monkeypatch):
    """Counts ``jax.device_put`` calls from here on; returns the log."""
    import jax
    log = []
    real = jax.device_put

    def put(x, *args, **kw):
        log.append(np.asarray(x).shape)
        return real(x, *args, **kw)
    monkeypatch.setattr(jax, "device_put", put)
    return log


@pytest.mark.parametrize("n_pairs,total_words", _EDGES)
def test_read_only_image_is_uploaded_once_and_answers_bit_identical(
        n_pairs, total_words, monkeypatch):
    """A read-only image is padded and uploaded on its first probe and
    serves every later probe from the device, with the same answers."""
    pytest.importorskip("jax")
    lo, hi, off, nw, bits = _ragged_case(n_pairs, total_words)
    bits.flags.writeable = False
    log = _uploads(monkeypatch)
    up0 = filters.transfers["uploads"]
    want = filters.probe_pairs_np(lo, hi, off, nw, bits, 7)
    for _ in range(3):
        got = filters.probe_pairs(lo, hi, off, nw, bits, 7, impl="jax")
        assert got.dtype == bool and (got == want).all()
    pw = filters.bucket(total_words, filters.MIN_WORDS_BUCKET)
    assert log == [(pw,)]
    assert filters.transfers["uploads"] - up0 == 1


def test_writeable_image_is_never_kept_on_the_device(monkeypatch):
    pytest.importorskip("jax")
    lo, hi, off, nw, bits = _ragged_case(40, 1500)
    log = _uploads(monkeypatch)
    before = dict(filters.transfers)
    for _ in range(3):
        got = filters.probe_pairs(lo, hi, off, nw, bits, 7, impl="jax")
        assert (got == filters.probe_pairs_np(lo, hi, off, nw, bits,
                                              7)).all()
    assert log == [] and id(bits) not in filters._resident
    assert filters.transfers["uploads"] == before["uploads"]
    # each call hands over its padded pairs and its padded image
    assert (filters.transfers["h2d_bytes"] - before["h2d_bytes"]
            == 3 * filters.padded_bytes(40, 1500))
    # a read-only view of a writeable array can still change: not kept
    view = bits[:]
    view.flags.writeable = False
    filters.probe_pairs(lo, hi, off, nw, view, 7, impl="jax")
    assert log == [] and id(view) not in filters._resident


def test_resident_images_stay_within_bound_and_go_with_their_host_array(
        monkeypatch):
    pytest.importorskip("jax")
    monkeypatch.setattr(filters, "RESIDENT_IMAGES", 3)
    monkeypatch.setattr(filters, "_resident", type(filters._resident)())
    lo, hi, off, nw, bits = _ragged_case(20, 1100)
    log = _uploads(monkeypatch)
    images = []
    for _ in range(5):
        img = bits.copy()
        img.flags.writeable = False
        images.append(img)
        filters.probe_pairs(lo, hi, off, nw, img, 7, impl="jax")
        assert len(filters._resident) <= 3
    assert len(log) == 5
    # the least recently used went first
    assert [id(i) for i in images[2:]] == list(filters._resident)
    filters.probe_pairs(lo, hi, off, nw, images[0], 7, impl="jax")
    assert len(log) == 6 and id(images[2]) not in filters._resident
    # a freed host image takes its device copy with it
    gone = id(images[-1])
    del images[-1], img
    assert gone not in filters._resident and len(filters._resident) == 2
    # and its owner may free it while the host array lives
    filters.release(images[0])
    assert id(images[0]) not in filters._resident
    got = filters.probe_pairs(lo, hi, off, nw, images[0], 7, impl="jax")
    assert len(log) == 7
    assert (got == filters.probe_pairs_np(lo, hi, off, nw, bits, 7)).all()


def test_concat_filters_returns_a_read_only_image():
    rng = np.random.default_rng(9)
    keys = rng.integers(0, 2**63, 300).astype(np.uint64)
    lo, hi = filters.split_hash(keys)

    class _Sst:
        def __init__(self, sid, words):
            self.sid, self.filter_words = sid, words
    a = _Sst(1, filters.build_filter_np(lo, hi, 64, 7))
    b = _Sst(2, filters.build_filter_np(hi, lo, 32, 7))
    for ssts in ([a, b], [a], []):
        bits, _ = filters.concat_filters(ssts)
        assert not bits.flags.writeable and bits.flags.owndata
    assert a.filter_words.flags.writeable


@pytest.mark.parametrize("n,floor,want", [
    (0, 256, 256), (1, 256, 256), (256, 256, 256), (257, 256, 512),
    (1023, 1024, 1024), (1025, 1024, 2048), (655360, 1024, 2 ** 20),
])
def test_bucket_is_next_power_of_two_above_floor(n, floor, want):
    assert filters.bucket(n, floor) == want


def test_have_jax_lets_only_import_errors_mean_absent(monkeypatch):
    """A missing jax selects numpy; any other failure propagates."""
    import builtins
    real_import = builtins.__import__

    def broken(name, *args, **kw):
        if name == "jax":
            raise RuntimeError("backend exploded")
        return real_import(name, *args, **kw)
    monkeypatch.setattr(filters, "_HAVE_JAX", None)
    monkeypatch.setattr(builtins, "__import__", broken)
    with pytest.raises(RuntimeError, match="backend exploded"):
        filters.resolve_impl("auto")
