"""Work of the ragged (key x filter) Bloom probe, from its shapes.

Counts what the probe has to do for the pairs asked, whatever implements
it: per pair the two hash halves, the filter's word offset and length are
read (4 bytes each), ``k`` filter words are gathered (4 bytes each) and one
hit flag is written (4 bytes).  Padding that an implementation adds to
reach a compiled shape is not work the caller asked for.

Operations are integer ALU work on the vector unit (per probe: multiply,
add, modulo, shift, add, shift, mask, and), for which no peak is
published, so the roofline of this kernel is its bytes over the HBM
bandwidth.
"""
from __future__ import annotations

INT_OPS_PER_PROBE = 8


def work(pairs: int, k_hashes: int) -> dict:
    """``{"flops", "int_ops", "bytes"}`` of one call over ``pairs`` pairs."""
    return {
        "flops": 0,
        "int_ops": pairs * k_hashes * INT_OPS_PER_PROBE,
        "bytes": pairs * (4 * 4 + 4 * k_hashes + 4),
    }


def ideal_seconds(pairs: int, k_hashes: int, peaks: dict) -> float:
    """Least time the chip could take: the probe is bound by its bytes."""
    return work(pairs, k_hashes)["bytes"] / peaks["hbm_bytes_per_s"]
