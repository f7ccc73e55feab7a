"""Random weights of a dense decoder from a seed, made on the device in one
jitted call, in bfloat16 as they are served, in the parameter layout the
serving engine reads (``embed``, ``final_norm``, ``layers`` stacked on a
leading layer axis).

Matrices are normal with standard deviation 1/sqrt(fan-in); norm gains
are 1 + 0.1 * normal, so that a path that skips a gain shows in the
comparison with the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.dense_lm import Dims

DTYPE = jnp.bfloat16


@functools.partial(jax.jit, static_argnames=("dims",))
def make(key, dims: Dims):
    L, d, hd = dims.layers, dims.hidden, dims.head_dim
    qd, kvd, f = dims.heads * hd, dims.kv_heads * hd, dims.ffn
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return jax.random.normal(next(ks), shape, DTYPE) \
            * jnp.asarray(fan_in ** -0.5, DTYPE)

    def gain(shape):
        return (1 + 0.1 * jax.random.normal(next(ks), shape,
                                            jnp.float32)).astype(DTYPE)

    return {
        "embed": mat((dims.vocab, d), d),
        "final_norm": gain((d,)),
        "layers": {
            "attn_norm": gain((L, d)),
            "mlp_norm": gain((L, d)),
            "attn": {"wq": mat((L, d, qd), d), "wk": mat((L, d, kvd), d),
                     "wv": mat((L, d, kvd), d), "wo": mat((L, qd, d), qd),
                     "q_norm": gain((L, hd)), "k_norm": gain((L, hd))},
            "mlp": {"w_gate": mat((L, d, f), d), "w_up": mat((L, d, f), d),
                    "w_down": mat((L, f, d), f)},
        },
    }
