"""Production MoE: shard_map dispatch with explicit expert-parallel a2a.

GSPMD cannot partition the dispatch scatter into an expert-sharded buffer
(it falls back to full-shape masked ops — 4 GiB u32 index tensors per
layer).  Real MoE frameworks hand-write this exchange; so do we:

EP path (num_experts % model_axis == 0):
  1. per device: local top-k + scatter into [E, C_src, d]  (local, clean)
  2. all_to_all over "model": split E, concat source shards
     -> [E/ep, ep*C_src, d]
  3. grouped GEMM with the local expert shard (weights FSDP-gathered
     over "data" inside the shard_map)
  4. all_to_all back + local combine.

TP fallback (E not divisible, e.g. mixtral's 8 experts on a 16-wide axis):
  every device runs all experts on its (batch x seq)-shard with
  d_ff-sharded weights; the down-projection psums over "model".

Activations enter and leave sequence-sharded P(dp, "model", None) — each
device dispatches only its seq shard, so dispatch buffers stay
O(T_local * k * d).  Capacity is per (expert, source shard), the standard
deployment semantics.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..config import ModelConfig


def _topk_dispatch(x, router, k: int, e: int, cap: int):
    """x: [T, d] -> buf [E, cap, d], (pos, keep, top_w, top_e)."""
    t, d = x.shape
    logits = x.astype(jnp.float32) @ router
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    flat_e = top_e.reshape(-1)                       # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    x_rep = jnp.broadcast_to(x[:, None], (t, k, d)).reshape(t * k, d)
    buf = jnp.zeros((e, cap, d), x.dtype)
    safe_pos = jnp.where(keep, pos, cap)
    buf = buf.at[flat_e, safe_pos].set(x_rep, mode="drop")
    return buf, flat_e, pos, keep, top_w


def _combine(out_rows, flat_e, pos, keep, top_w, cap: int, t: int, k: int):
    """out_rows: [E*cap, d] flattened expert outputs -> [T, d]."""
    idx = flat_e * cap + jnp.minimum(pos, cap - 1)
    gathered = out_rows[idx]                         # [T*k, d]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w = top_w.reshape(-1)[:, None].astype(gathered.dtype)
    # tok_idx is repeat(arange(t), k): combine is a reshape + sum, no scatter
    return jnp.sum((gathered * w).reshape(t, k, -1), axis=1)


def moe_shard_map(p, cfg: ModelConfig, x: jnp.ndarray, mesh: Mesh,
                  dp) -> jnp.ndarray:
    """x: [B, S, d] sharded P(dp, "model", None). Returns same sharding."""
    e, k = cfg.num_experts, cfg.top_k
    ep = mesh.shape["model"]
    b, s, d = x.shape
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]
    t_loc = (b // dp_size) * (s // ep)
    cap = max(int(cfg.capacity_factor * t_loc * k / e), 1)
    expert_parallel = (e % ep == 0)

    wspecs = {
        "router": P("data", None),
        "we_gate": P("model", "data", None) if expert_parallel
        else P(None, "data", "model"),
        "we_up": P("model", "data", None) if expert_parallel
        else P(None, "data", "model"),
        "we_down": P("model", None, "data") if expert_parallel
        else P(None, "model", "data"),
    }
    x_spec = P(dp, "model", None)

    def ep_body(xl, router, wg, wu, wd):
        # xl: [B_loc, S_loc, d]; wg: [E/ep, d/dp, f]
        router = jax.lax.all_gather(router, "data", axis=0, tiled=True)
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        bl, sl, _ = xl.shape
        xf = xl.reshape(bl * sl, d)
        buf, flat_e, pos, keep, top_w = _topk_dispatch(xf, router, k, e, cap)
        # exchange: rows to their expert's shard
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)          # [E/ep, ep*cap, d]
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)       # [E/ep, ep*cap, d]
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)          # [E, cap, d]
        y = _combine(out.reshape(e * cap, d), flat_e, pos, keep, top_w,
                     cap, bl * sl, k)
        return y.reshape(bl, sl, d).astype(xl.dtype)

    def tp_body(xl, router, wg, wu, wd):
        # xl: [B_loc, S_loc, d] seq-sharded; wg: [E, d/dp, f/ep].
        # With f TP-sharded, every model shard must see the SAME tokens:
        # gather the sequence, run all experts on the full local batch with
        # the f-shard, and psum_scatter the partial outputs back onto the
        # sequence sharding (Megatron-style MoE tensor parallelism).
        router = jax.lax.all_gather(router, "data", axis=0, tiled=True)
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        x_full = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
        bl, s_full, _ = x_full.shape
        t_full = bl * s_full
        cap_tp = max(int(cfg.capacity_factor * t_full * k / e), 1)
        xf = x_full.reshape(t_full, d)
        buf, flat_e, pos, keep, top_w = _topk_dispatch(xf, router, k, e,
                                                       cap_tp)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)     # [E, cap, f/ep]
        out = jnp.einsum("ecf,efd->ecd", h, wd)       # partial over f
        y = _combine(out.reshape(e * cap_tp, d), flat_e, pos, keep, top_w,
                     cap_tp, t_full, k)               # [T, d] partial
        y = y.reshape(bl, s_full, d)
        y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                 tiled=True)          # summed + seq-sharded
        return y.astype(xl.dtype)

    body = ep_body if expert_parallel else tp_body
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(x_spec, wspecs["router"], wspecs["we_gate"],
                                 wspecs["we_up"], wspecs["we_down"]),
                       out_specs=x_spec,
                       check_vma=False)
    return fn(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
