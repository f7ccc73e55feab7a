"""Real packed Bloom filters with one unified hash family (splitmix64).

Every SST carries a packed uint32 bit array built from its key set
(``filter_bits_per_key`` bits per key, ``k = round(bits_per_key * ln 2)``
probe positions).  The hash family is shared across every implementation:

* keys are pre-hashed **host-side** with the same splitmix64 finaliser the
  injected-FP oracle already uses (``sstable._mix64``) — uint64 hashing
  never happens on the accelerator, where 64-bit lanes are unavailable;
* the 64-bit hash is split into two uint32 halves ``lo = h & 0xffffffff``
  and ``hi = (h >> 32) | 1`` (forced odd so the probe stride cycles);
* probe position ``i`` is Kirsch-Mitzenmacher double hashing,
  ``pos_i = (lo + i * hi) mod (num_words * 32)``, computed in wrapping
  uint32 arithmetic — bit-for-bit identical in the pure-numpy fallback
  here, the jnp oracle (``repro.kernels.bloom_probe.ref``), and the Pallas
  kernel (``repro.kernels.bloom_probe``).

The numpy fallback is the simulator default (no jax import required);
``impl="jax"`` runs the batched probe as one jitted call on the default
JAX device (:func:`probe_pairs_device`), and the cross-implementation
agreement is asserted by ``tests/test_filters.py``.
"""
from __future__ import annotations

import math
import weakref
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..obs.spans import span
from .sstable import SST, _mix64

_LN2 = math.log(2.0)
_M64 = (1 << 64) - 1
_M32 = (1 << 32) - 1


# ----------------------------------------------------------------------
# hashing
# ----------------------------------------------------------------------
def split_hash(keys) -> Tuple[np.ndarray, np.ndarray]:
    """splitmix64 the uint64 keys, split into (lo, hi) uint32 halves.

    ``hi`` is forced odd so the double-hashing stride is coprime with any
    power-of-two and never collapses the k probe positions onto one bit.
    """
    keys = np.atleast_1d(np.asarray(keys, dtype=np.uint64))
    h = _mix64(keys)
    lo = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (h >> np.uint64(32)).astype(np.uint32) | np.uint32(1)
    return lo, hi


def _split_hash_int(key: int) -> Tuple[int, int]:
    """Python-int twin of :func:`split_hash` for the per-key read path."""
    x = key & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    x = x ^ (x >> 31)
    return x & _M32, (x >> 32) | 1


def filter_params(num_keys: int, bits_per_key: int) -> Tuple[int, int]:
    """(num_words, k_hashes) for a key count at a bits-per-key budget."""
    nbits = max(1, int(num_keys)) * max(1, int(bits_per_key))
    num_words = max(1, -(-nbits // 32))
    k = max(1, min(16, int(round(bits_per_key * _LN2))))
    return num_words, k


# ----------------------------------------------------------------------
# pure-numpy build + probe (the simulator default; no jax required)
# ----------------------------------------------------------------------
def build_filter_np(lo: np.ndarray, hi: np.ndarray, num_words: int,
                    k_hashes: int) -> np.ndarray:
    """Set k bits per key on a packed uint32 array (same packing as the
    jnp oracle: word ``w`` bit ``b`` lives at flat index ``w*32 + b``)."""
    nbits = np.uint32(num_words * 32)
    flat = np.zeros(num_words * 32, dtype=bool)
    with np.errstate(over="ignore"):
        for i in range(k_hashes):
            pos = (lo + np.uint32(i) * hi) % nbits
            flat[pos.astype(np.int64)] = True
    lanes = flat.reshape(num_words, 32).astype(np.uint32)
    weights = np.uint32(1) << np.arange(32, dtype=np.uint32)
    return np.sum(lanes * weights, axis=-1, dtype=np.uint32)


def probe_np(lo: np.ndarray, hi: np.ndarray, bits: np.ndarray,
             k_hashes: int) -> np.ndarray:
    """Probe one filter with a batch of pre-hashed keys -> bool[N]."""
    nbits = np.uint32(bits.shape[0] * 32)
    hit = np.ones(lo.shape, dtype=bool)
    with np.errstate(over="ignore"):
        for i in range(k_hashes):
            pos = (lo + np.uint32(i) * hi) % nbits
            w = bits[(pos >> np.uint32(5)).astype(np.int64)]
            hit &= ((w >> (pos & np.uint32(31))) & np.uint32(1)).astype(bool)
    return hit


def probe_pairs_np(lo: np.ndarray, hi: np.ndarray, word_off: np.ndarray,
                   num_words: np.ndarray, bits_concat: np.ndarray,
                   k_hashes: int) -> np.ndarray:
    """Probe P (key x filter) pairs in one vectorized call.

    ``bits_concat`` is the concatenation of every candidate SST's filter
    words; pair ``p`` probes the ``num_words[p]`` words starting at
    ``word_off[p]``.  This is the ragged form the batched read path needs:
    each key may probe a different filter per level.
    """
    nbits = (num_words.astype(np.uint32) * np.uint32(32))
    off = word_off.astype(np.int64)
    hit = np.ones(lo.shape, dtype=bool)
    with np.errstate(over="ignore"):
        for i in range(k_hashes):
            pos = (lo + np.uint32(i) * hi) % nbits
            w = bits_concat[off + (pos >> np.uint32(5)).astype(np.int64)]
            hit &= ((w >> (pos & np.uint32(31))) & np.uint32(1)).astype(bool)
    return hit


def probe_one_np(key: int, bits: np.ndarray, k_hashes: int) -> bool:
    """Scalar probe in plain python ints — the per-key `get` fast path.

    Bitwise-identical to :func:`probe_np` on a length-1 batch (asserted by
    ``tests/test_filters.py``); avoids numpy array overhead per get.
    """
    lo, hi = _split_hash_int(key)
    nbits = bits.shape[0] * 32
    for i in range(k_hashes):
        pos = ((lo + i * hi) & _M32) % nbits
        if not (int(bits[pos >> 5]) >> (pos & 31)) & 1:
            return False
    return True


# ----------------------------------------------------------------------
# jax route (the batched probe on the default JAX device) — bit-identical
# ----------------------------------------------------------------------
_HAVE_JAX: Optional[bool] = None

# smallest padded sizes of the device probe: the pair count and the
# filter-image length are rounded up to powers of two no smaller than
# these, so a whole run compiles a handful of shapes however many
# batches it probes
MIN_PAIRS_BUCKET = 256
MIN_WORDS_BUCKET = 1024
# bytes one padded pair hands the device: lo, hi, word offset and word
# count, 4 bytes each; a filter word is 4 bytes
PAIR_BYTES = 16
WORD_BYTES = 4
# padded device copies of read-only filter images, least recently used
# first: id(host image) -> (weak reference to it, device array).  An entry
# goes when its host image is freed, when its owner releases it
# (:func:`release`), or when more than RESIDENT_IMAGES are held: the
# images of a few trees (one each, ``LSMTree._tree_image``)
_resident: "OrderedDict[int, tuple]" = OrderedDict()
RESIDENT_IMAGES = 14
# what the device route has handed the device since the process started:
# filter images uploaded to stay resident, and bytes of every array handed
# over (pairs, per-call images and uploads, padding included)
transfers = {"uploads": 0, "h2d_bytes": 0}


def have_jax() -> bool:
    """Whether jax imports.  Only a missing jax counts as absent: any other
    failure propagates, and the device route itself raises if the backend
    cannot start."""
    global _HAVE_JAX
    if _HAVE_JAX is None:
        try:
            import jax  # noqa: F401
        except ImportError:
            _HAVE_JAX = False
        else:
            _HAVE_JAX = True
    return _HAVE_JAX


def resolve_impl(impl: str) -> str:
    """"auto" -> the device route when jax imports, else numpy."""
    if impl == "auto":
        return "jax" if have_jax() else "numpy"
    if impl not in ("numpy", "jax"):
        raise ValueError(f"unknown filter impl {impl!r}")
    return impl


def bucket(n: int, floor: int) -> int:
    """Smallest power of two >= ``n`` and >= ``floor``."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


def padded_sizes(pairs: int, words: int) -> Tuple[int, int]:
    """(pairs, words) as the device probe pads them."""
    return bucket(pairs, MIN_PAIRS_BUCKET), bucket(words, MIN_WORDS_BUCKET)


def padded_bytes(pairs: int, words: int) -> int:
    """Bytes a device probe call of ``pairs`` pairs hands the device when
    it hands over its filter image of ``words`` words too, padding
    included: every call with a writeable image, and the call that uploads
    a read-only one.  A call against a resident image hands over only the
    pairs, ``PAIR_BYTES`` each."""
    pp, pw = padded_sizes(pairs, words)
    return PAIR_BYTES * pp + WORD_BYTES * pw


def _pad(a, size, dtype, fill=0):
    out = np.full(size, fill, dtype=dtype)
    out[:len(a)] = a
    return out


def _frozen(bits) -> bool:
    """Whether ``bits`` is a filter image whose words cannot change: a
    read-only array that owns its memory (``concat_filters``' images).  A
    read-only view of a writeable array is not."""
    return (isinstance(bits, np.ndarray) and not bits.flags.writeable
            and bits.flags.owndata)


def _drop(key: int, ref) -> None:
    """Forget the entry of ``key`` if it is still the one ``ref`` names."""
    hit = _resident.get(key)
    if hit is not None and hit[0] is ref:
        del _resident[key]


def _resident_image(bits: np.ndarray, pw: int):
    """The padded device copy of a frozen host image, uploaded on its first
    probe.  The upload is uncommitted (``device_put`` with no device), so
    the executables compiled for numpy arguments serve it unchanged."""
    key = id(bits)
    hit = _resident.get(key)
    if hit is not None and hit[0]() is bits:
        _resident.move_to_end(key)
        return hit[1]
    import jax
    with span("probe.upload", words=pw):
        image = jax.device_put(_pad(bits, pw, np.uint32))
    ref = weakref.ref(bits, lambda r, key=key: _drop(key, r))
    _resident[key] = (ref, image)
    while len(_resident) > RESIDENT_IMAGES:
        _resident.popitem(last=False)
    transfers["uploads"] += 1
    transfers["h2d_bytes"] += WORD_BYTES * pw
    return image


def release(bits) -> None:
    """Free the device copy of a host image its owner will probe no more."""
    hit = _resident.get(id(bits))
    if hit is not None and hit[0]() is bits:
        del _resident[id(bits)]


def probe_pairs_device(lo, hi, word_off, num_words, bits_concat, k_hashes):
    """The ragged pairs probe as one jitted call on the default JAX device.

    Pairs and filter words are zero-padded to :func:`padded_sizes`; padded
    pairs probe ``off=0, num_words=1``.  A frozen image (read-only, owning
    its memory) is padded and uploaded once and stays on the device while
    its host array lives; any other image is padded and handed over on
    every call.  Returns the padded int32 hit mask as a device array: its
    first ``len(lo)`` entries answer the pairs.
    """
    from ..kernels.bloom_probe.ops import probe_pairs as probe_pairs_jit
    pp, pw = padded_sizes(len(lo), len(bits_concat))
    resident = _frozen(bits_concat)
    image = _resident_image(bits_concat, pw) if resident else None
    with span("probe.pad", words=pw):
        args = (_pad(lo, pp, np.uint32), _pad(hi, pp, np.uint32),
                _pad(word_off, pp, np.int32),
                _pad(num_words, pp, np.uint32, fill=1))
        if not resident:
            image = _pad(bits_concat, pw, np.uint32)
    transfers["h2d_bytes"] += (PAIR_BYTES * pp if resident else
                               padded_bytes(len(lo), len(bits_concat)))
    with span("probe.call"):
        return probe_pairs_jit(*args, image, k_hashes=int(k_hashes))


def probe_pairs(lo, hi, word_off, num_words, bits_concat, k_hashes,
                impl: str = "numpy") -> np.ndarray:
    """Dispatch the ragged pairs probe to the selected implementation."""
    if resolve_impl(impl) == "jax":
        out = probe_pairs_device(lo, hi, word_off, num_words, bits_concat,
                                 k_hashes)
        with span("probe.read"):
            return np.asarray(out)[:len(lo)].astype(bool)
    return probe_pairs_np(lo, hi, word_off, num_words, bits_concat, k_hashes)


# ----------------------------------------------------------------------
# SST attachment
# ----------------------------------------------------------------------
def attach_filter(sst: SST, bits_per_key: int) -> None:
    """Build and attach the packed filter for an SST's key set."""
    with span("filter.build"):
        num_words, k = filter_params(sst.num_objs, bits_per_key)
        lo, hi = split_hash(sst.keys)
        sst.filter_words = build_filter_np(lo, hi, num_words, k)
        sst.filter_k = k


def concat_filters(ssts: Sequence[SST]) -> Tuple[np.ndarray, dict]:
    """Concatenate distinct SSTs' filter words for the pairs probe.

    Returns (bits_concat, {sid: (word_off, num_words)}); ``bits_concat``
    is read-only.
    """
    offsets: dict = {}
    chunks: List[np.ndarray] = []
    off = 0
    for sst in ssts:
        if sst.sid in offsets or sst.filter_words is None:
            continue
        w = sst.filter_words
        offsets[sst.sid] = (off, len(w))
        chunks.append(w)
        off += len(w)
    bits = (np.concatenate(chunks) if chunks
            else np.zeros(0, dtype=np.uint32))
    # frozen, so the device route may keep it resident (probe_pairs_device)
    bits.flags.writeable = False
    return bits, offsets
