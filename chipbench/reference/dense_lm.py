"""Plain float32 reference of a dense decoder (Qwen3 layout: GQA attention
with RMSNorm on each query and key head, rotary positions by halves,
SwiGLU MLP, output head tied to the embedding).  Imports nothing of the
program; it reads the weights the benchmark made, upcast to float32, and
computes every product at the highest matmul precision.

``quant`` makes the control: the same forward with every weight matrix
first rounded to int8 (symmetric, one scale per output channel) or to
float8 e4m3 (one scale per output channel), and computed as above.
``kv_dtype`` makes the control of the KV pool: each layer's keys and
values held in that type (bfloat16 below the configuration's float32),
attended from it and returned in it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float


def dims_of(hf: dict) -> Dims:
    """From a Hugging Face ``config.json`` of the Qwen3 family."""
    return Dims(hf["num_hidden_layers"], hf["hidden_size"],
                hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"], hf["intermediate_size"], hf["vocab_size"],
                float(hf["rope_theta"]), float(hf["rms_norm_eps"]))


def _round(w, quant: Optional[str], out_axis: int):
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    red = tuple(a for a in range(w.ndim) if a != out_axis % w.ndim)
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
    if quant == "int8":
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown quant {quant!r}")


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, theta):
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("dims", "quant", "kv_dtype"))
def forward(params, tokens, sel, dims: Dims, quant: Optional[str] = None,
            kv_dtype: Optional[str] = None):
    """``tokens`` [B, S] from position 0.  Returns the logits at positions
    ``sel`` [B, R] ([B, R, vocab]) and every layer's post-RoPE keys and
    values ([layers, B, S, kv_heads, head_dim] each)."""
    b, s = tokens.shape
    h, kv, hd = dims.heads, dims.kv_heads, dims.head_dim
    g = h // kv
    embed = _round(params["embed"], quant, 0)
    x = embed[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = p["attn"]
        y = _norm(x, p["attn_norm"], dims.eps)
        q = _mm(y, _round(a["wq"], quant, -1)).reshape(b, s, h, hd)
        k = _mm(y, _round(a["wk"], quant, -1)).reshape(b, s, kv, hd)
        v = _mm(y, _round(a["wv"], quant, -1)).reshape(b, s, kv, hd)
        q = _rope(_norm(q, a["q_norm"], dims.eps), dims.rope_theta)
        k = _rope(_norm(k, a["k_norm"], dims.eps), dims.rope_theta)
        held = (k, v) if kv_dtype is None else (k.astype(kv_dtype),
                                                v.astype(kv_dtype))
        k, v = (t.astype(jnp.float32) for t in held)
        qg = q.reshape(b, s, kv, g, hd)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                            precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", probs, v, precision=HIGHEST)
        x = x + _mm(o.reshape(b, s, h * hd), _round(a["wo"], quant, -1))
        m = p["mlp"]
        y = _norm(x, p["mlp_norm"], dims.eps)
        act = jax.nn.silu(_mm(y, _round(m["w_gate"], quant, -1))) \
            * _mm(y, _round(m["w_up"], quant, -1))
        return x + _mm(act, _round(m["w_down"], quant, -1)), held

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    rows = jnp.take_along_axis(x, sel[..., None], axis=1)
    rows = _norm(rows, params["final_norm"], dims.eps)
    return _mm(rows, embed.T), ks, vs
