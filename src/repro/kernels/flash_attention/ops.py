"""Jit'd public wrapper for the flash attention kernel.

On an accelerator this compiles the Pallas kernel; on the CPU backend it
runs in interpret mode (see ``repro.kernels.default_interpret``).  The
backward pass is a custom VJP that recomputes attention with the reference
implementation — numerically exact, memory-light (flash-style recompute).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import default_interpret
from .flash_attention import flash_attention_fwd
from .ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: [B, H, S, D]; k/v: [B, KV, S, D] -> [B, H, S, D]."""
    interp = default_interpret() if interpret is None else interpret
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interp)


def _fwd(q, k, v, causal, window, interpret):
    out = flash_attention(q, k, v, causal, window, interpret)
    return out, (q, k, v)


def _bwd(causal, window, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
