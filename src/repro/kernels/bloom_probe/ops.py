"""Jit'd public wrappers for the Bloom probes."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .. import default_interpret
from .bloom_probe import bloom_probe
from .ref import bloom_probe_pairs_ref


@functools.partial(jax.jit, static_argnames=("k_hashes", "interpret"))
def probe(lo, hi, bits, k_hashes: int = 7,
          interpret: Optional[bool] = None):
    """Probe a packed filter with pre-hashed keys (see ``bloom_probe``)."""
    interp = default_interpret() if interpret is None else interpret
    return bloom_probe(lo, hi, bits, k_hashes=k_hashes, interpret=interp)


# the store's batched device probe: the jnp ragged-pairs oracle, compiled
# once per (pairs, filter words, k) shape — ``repro.lsm.filters`` pads
# both sizes to power-of-two buckets so only a handful of shapes exist
probe_pairs = jax.jit(bloom_probe_pairs_ref, static_argnames=("k_hashes",))
