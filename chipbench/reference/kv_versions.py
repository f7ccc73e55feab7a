"""Plain versioned reference for the hinted LSM store: every acknowledged
write of each key, in acknowledgement order, and what a read may return.
Imports nothing of the program.

The store's guarantees, as the configuration ``hhzs-paper-1kib-rw``
states them: an acknowledged write is read back at its newest version; a
read never returns a version older than the newest write acknowledged
before the read was issued; a read returns only a version written to its
key.  A version is the configuration's 8-byte stamp, one per write: the
load writes key ``k`` as ``k << 32``; every later write is acknowledged
here with its own stamp.  Writes and read issues are ordered by one
counter the harness keeps (``order``), so "before" is exact where the
virtual clock ties.

The Bloom probe is ``kv_set.probe_pairs``: the filter format is the same.
"""
from __future__ import annotations

from bisect import bisect_left
from typing import Dict, List, Optional, Tuple

from chipbench.reference.kv_set import probe_pairs  # noqa: F401

LOAD = -1       # the order of every load write: before anything the harness saw


def load_stamp(key: int) -> int:
    return int(key) << 32


class Versions:
    """Keys ``0 .. n-1`` were loaded with their load stamps; ``ack`` adds
    each later write in acknowledgement order.  ``bad_stamps`` counts the
    acknowledged writes whose stamp is not a version of their own: none,
    one whose high 32 bits are not the key, or one the key already holds
    (its load stamp included).  Such a write would let an older version
    pass for a newer one, so the stamps the program makes are checked
    here, not assumed."""

    def __init__(self, n: int):
        self.n = n
        self.orders: Dict[int, List[int]] = {}     # key -> ack orders
        self.stamps: Dict[int, List[int]] = {}     # key -> stamps, same order
        self.order_of: Dict[Tuple[int, int], int] = {}   # (key, stamp) -> order
        self.bad_stamps = 0

    def ack(self, key: int, stamp: Optional[int], order: int) -> None:
        key = int(key)
        if stamp is None:
            self.bad_stamps += 1
            return
        stamp = int(stamp)
        if key not in self.orders:
            self.orders[key], self.stamps[key] = [], []
            if 0 <= key < self.n:
                self._add(key, load_stamp(key), LOAD)
        if self.orders[key] and order <= self.orders[key][-1]:
            raise ValueError("acknowledgements out of order")
        if stamp >> 32 != key or (key, stamp) in self.order_of:
            self.bad_stamps += 1
        self._add(key, stamp, order)

    def _add(self, key: int, stamp: int, order: int) -> None:
        self.orders[key].append(order)
        self.stamps[key].append(stamp)
        self.order_of[(key, stamp)] = order

    def newest(self, key: int, before: Optional[int] = None) -> Optional[int]:
        """The stamp of the newest write of ``key`` acknowledged before
        ``before`` (all of them when None); None where there is none."""
        key = int(key)
        if key not in self.orders:
            return load_stamp(key) if 0 <= key < self.n else None
        orders = self.orders[key]
        i = len(orders) if before is None else bisect_left(orders, before)
        return self.stamps[key][i - 1] if i else None

    def read_is_wrong(self, key: int, issued: int, found: bool,
                      value) -> bool:
        """Whether a read of ``key`` issued at ``issued`` broke the
        guarantees: "not found" for a key that holds a version, a value
        never written to the key, or one older than the newest write
        acknowledged before the read was issued."""
        want = self.newest(key, issued)
        if not found or value is None:
            return want is not None
        key = int(key)
        try:
            order = self.order_of.get((key, int(value)))
        except (TypeError, ValueError):
            return True
        if order is None:
            order = LOAD if (key not in self.orders and 0 <= key < self.n
                             and int(value) == load_stamp(key)) else None
        if order is None:
            return True
        return want is not None and order < self.order_of.get(
            (key, want), LOAD)
