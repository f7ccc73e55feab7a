"""Plain reference for the hinted LSM store: a set of acknowledged writes
and a plain Bloom probe.  Imports nothing of the program.

The store's guarantees, as the configuration states them: every key that
was written and not deleted is found, no other key is found, and a Bloom
filter never rejects a key it holds.  The probe below follows the filter
format the configuration names (packed uint32 words, bit ``b`` of word
``w`` at flat position ``32 w + b``; ``k`` positions by double hashing
``(lo + i hi) mod (32 num_words)`` in wrapping 32-bit arithmetic).
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

M32 = (1 << 32) - 1


class LoadedKeys:
    """Keys ``0 .. n-1`` were loaded; later writes and deletes are kept
    apart.  ``holds(key)`` is what a read must answer."""

    def __init__(self, n: int):
        self.n = n
        self.written: set = set()
        self.deleted: set = set()

    def put(self, key: int) -> None:
        self.written.add(key)
        self.deleted.discard(key)

    def delete(self, key: int) -> None:
        self.deleted.add(key)
        self.written.discard(key)

    def holds(self, key: int) -> bool:
        if key in self.deleted:
            return False
        return 0 <= key < self.n or key in self.written


def probe_pairs(lo, hi, word_off, num_words, words: np.ndarray,
                k: int) -> np.ndarray:
    """Whether each pair's filter may hold its key: pair ``p`` tests the
    key hashed to (``lo[p]``, ``hi[p]``) against the filter of
    ``num_words[p]`` words at ``word_off[p]`` of ``words``.  64-bit
    arithmetic, masked to 32 bits where the format wraps."""
    lo = np.asarray(lo, np.uint64)
    hi = np.asarray(hi, np.uint64)
    off = np.asarray(word_off, np.int64)
    nbits = np.asarray(num_words, np.uint64) * np.uint64(32)
    hit = np.ones(len(lo), bool)
    for i in range(k):
        pos = ((lo + np.uint64(i) * hi) & np.uint64(M32)) % nbits
        word = np.asarray(words)[off + (pos >> np.uint64(5)).astype(np.int64)]
        word = word.astype(np.uint64)
        hit &= ((word >> (pos & np.uint64(31))) & np.uint64(1)) == 1
    return hit


def wrong_answers(keys: Iterable[int], found: Iterable[bool],
                  model: LoadedKeys) -> int:
    return sum(bool(f) != model.holds(int(k)) for k, f in zip(keys, found))
