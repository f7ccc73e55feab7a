"""Continuous-batching serving engine over HHZS-tiered paged KV.

A compact but real engine: request queue -> admission -> prefill ->
interleaved decode with continuous batching.  The KV cache is paged and
two-tier (HBM/host) under the HHZS-style manager; attention runs in jnp
(``models.layers.sdpa``) over the KV gathered from the sequence's pages.
Preemption on HBM pressure *is* capacity migration; resumption
*is* popularity migration; prefix caching covers resumed sequences' first
pages — the paper's three techniques, end to end, on the serving path.

Deliberately single-host/single-stream (the multi-chip serving path is the
dry-run's serve_step); used by examples/serve_paged.py and the tests.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

try:                                    # the real engine needs jax; the
    import jax                          # sim serving path (workloads/
    import jax.numpy as jnp             # serving.py) does not
    from ..models import layers as L
    from ..models import model as M
except ImportError:                     # pragma: no cover - no-jax CI leg
    jax = None

from ..config import ModelConfig
from .paged_kv import PagedPool
from .tiering import HHZSKVManager


def _layer_forward(cfg: ModelConfig, layers, li, x, positions, pk, pv):
    """Layer ``li`` over new tokens ``x`` [1, T, d] attending to the
    resident KV ``pk``/``pv`` [L, S_prev, KV, D] plus their own.  Returns
    (x, k, v) with the new tokens' k/v [T, KV, D].  The layer index is
    traced, so every layer shares one program per (T, S_prev)."""
    layer = jax.tree.map(lambda a: a[li], layers)
    h = L.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q, k, v = L._project_qkv(layer["attn"], cfg, h, h)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    full_k = jnp.concatenate([pk[li], k[0]], axis=0)[None]
    full_v = jnp.concatenate([pv[li], v[0]], axis=0)[None]
    out = L.sdpa(q, full_k, full_v, cfg.num_heads // cfg.num_kv_heads,
                 causal=True, q_offset=pk.shape[1])
    x = x + out @ layer["attn"]["wo"]
    h = L.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    return x + L.mlp(layer["mlp"], cfg, h), k[0], v[0]


def _take_pages(a, pages, n: int):
    """The first ``n`` tokens held in ``pages`` of a pool array
    [L, P, page, KV, D], all layers: [L, n, KV, D]."""
    a = a[:, pages]
    return a.reshape(a.shape[0], -1, *a.shape[3:])[:, :n]


if jax is not None:
    _layer_forward = jax.jit(_layer_forward, static_argnums=(0,))
    _take_pages_device = jax.jit(_take_pages, static_argnums=(2,))


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # int32 tokens
    max_new_tokens: int
    out_tokens: List[int] = field(default_factory=list)
    state: str = "queued"            # queued | running | paused | done
    enqueued_step: int = 0

    @property
    def length(self) -> int:
        return len(self.prompt) + len(self.out_tokens)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 hbm_zones: int = 8, host_zones: int = 64,
                 pages_per_zone: int = 4, page_size: int = 16,
                 max_batch: int = 4, cache_zones: int = 1,
                 use_kernel: bool = False, seed: int = 0):
        if jax is None:
            raise RuntimeError("ServingEngine requires jax; the jax-free "
                               "serving path is repro.workloads.serving")
        assert cfg.family in ("dense",), "engine demo supports dense archs"
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        mk = lambda name, zones, host: PagedPool(
            name, cfg.num_layers, zones, pages_per_zone, page_size,
            cfg.num_kv_heads, cfg.head_dim_, host=host)
        self.hbm = mk("hbm", hbm_zones, host=False)
        self.host = mk("host", host_zones, host=True)
        self.mgr = HHZSKVManager(self.hbm, self.host,
                                 cache_zones=cache_zones)
        self.max_batch = max_batch
        self.use_kernel = use_kernel
        self.queue: List[Request] = []
        self.running: List[Request] = []
        self.done: List[Request] = []
        self.steps = 0
        self.tokens_out = 0

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.enqueued_step = self.steps
        self.queue.append(req)

    # ------------------------------------------------------------------
    def _forward_tokens(self, req: Request, tokens: np.ndarray) -> int:
        """Run tokens through the model, appending KV to the paged store.
        Returns the argmax next token after the last position."""
        cfg, p = self.cfg, self.params
        seq = self.mgr.seqs[req.rid]
        x = p["embed"][jnp.asarray(tokens)[None, :]]     # [1, T, d]
        positions = (jnp.arange(len(tokens)) + seq.length)[None, :]
        pk, pv = self._gather_kv(req)                     # [L, S_prev, KV, D]
        ks, vs = [], []
        for li in range(cfg.num_layers):
            x, k, v = _layer_forward(cfg, p["layers"], jnp.int32(li), x,
                                     positions, pk, pv)
            ks.append(k)
            vs.append(v)
        # append KV token by token (zone write pointers advance append-only)
        lk = np.asarray(jnp.stack(ks))                    # [L, T, KV, D]
        lv = np.asarray(jnp.stack(vs))
        for t in range(len(tokens)):
            zone = self.mgr.writable_zone(seq)
            pool = self.mgr.pool_of(seq)
            pool.write_token(zone, lk[:, t], lv[:, t])
            seq.length += 1
        x = L.rms_norm(x, p["final_norm"], cfg.norm_eps)
        logits = x[0, -1] @ M.lm_head(cfg, p)
        return int(jnp.argmax(logits))

    def _gather_kv(self, req: Request):
        """All resident KV of a sequence: ([L, S, KV, D], [L, S, KV, D]).
        Host-tier pages are gathered on the host and copied over once;
        HBM-tier pages are gathered on the device."""
        seq = self.mgr.seqs[req.rid]
        pool = self.mgr.pool_of(seq)
        n = seq.length
        pages = [pg for z in seq.zones for pg in z.pages]
        pages = pages[:-(-n // self.page_size)]
        if not pages:
            d = (pool.k.shape[0], 0, self.cfg.num_kv_heads,
                 self.cfg.head_dim_)
            return jnp.zeros(d, jnp.float32), jnp.zeros(d, jnp.float32)
        if isinstance(pool.k, np.ndarray):
            return (jnp.asarray(_take_pages(pool.k, pages, n)),
                    jnp.asarray(_take_pages(pool.v, pages, n)))
        idx = jnp.asarray(pages, jnp.int32)
        return (_take_pages_device(pool.k, idx, n),
                _take_pages_device(pool.v, idx, n))

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine iteration: admit, prefill one, decode all running."""
        self.steps += 1
        # admission
        while self.queue and len(self.running) < self.max_batch:
            req = self.queue.pop(0)
            self.mgr.on_prefill(req.rid, len(req.prompt))
            nxt = self._forward_tokens(req, req.prompt)
            req.out_tokens.append(nxt)
            req.state = "running"
            self.running.append(req)
            self.tokens_out += 1
        # migration tick with the active set
        self.mgr.tick([r.rid for r in self.running])
        # decode one token for every running sequence
        for req in list(self.running):
            nxt = self._forward_tokens(
                req, np.asarray([req.out_tokens[-1]], np.int32))
            req.out_tokens.append(nxt)
            self.tokens_out += 1
            if len(req.out_tokens) >= req.max_new_tokens:
                req.state = "done"
                self.running.remove(req)
                self.done.append(req)
                self.mgr.release(req.rid)

    def run(self, max_steps: int = 100) -> Dict:
        while (self.queue or self.running) and self.steps < max_steps:
            self.step()
        st = dict(self.mgr.stats)
        st.update(steps=self.steps, tokens_out=self.tokens_out,
                  done=len(self.done),
                  hbm_free_zones=self.hbm.num_free(),
                  host_free_zones=self.host.num_free())
        return st
