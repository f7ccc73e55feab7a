"""Jit'd public wrapper for the selective scan kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .. import default_interpret
from .selective_scan import selective_scan
from .ref import selective_scan_ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_scan(dt, bx, c, a, interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    return selective_scan(dt, bx, c, a, interpret=interp)
