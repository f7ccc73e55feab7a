"""Jit'd public wrapper for paged decode attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .. import default_interpret
from .paged_attention import paged_attention_decode
from .ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    return paged_attention_decode(q, k_pages, v_pages, block_tables,
                                  context_lens, interpret=interp)
