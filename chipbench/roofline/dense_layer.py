"""Work of one dense decoder layer (GQA attention with qk-norm, SwiGLU MLP)
over ``t`` new tokens that attend to ``s`` resident tokens, from shapes.

FLOPs: the four attention projections and the three MLP matmuls
(2 per multiply-add), plus causal attention, in which new token ``i``
scores and mixes ``s + i + 1`` keys.  Norms, RoPE and softmax are left
out: they are a fraction of a percent at these widths.

Bytes: the layer's weights once, the resident K and V once, the new
tokens' K and V written once, and the residual stream read and written.
That is what any implementation must move; copies it makes on the way
(a concatenated K, a gathered page) are not counted.

``dims`` keys: ``hidden``, ``heads``, ``kv_heads``, ``head_dim``,
``ffn``, ``weight_bytes``, ``kv_bytes``, ``act_bytes``.
"""
from __future__ import annotations


def layer_params(dims: dict) -> int:
    d, hd = dims["hidden"], dims["head_dim"]
    attn = d * dims["heads"] * hd * 2 + d * dims["kv_heads"] * hd * 2
    return attn + 3 * d * dims["ffn"] + 2 * d + 2 * hd


def work(t: int, s: int, dims: dict) -> dict:
    """``{"flops", "bytes"}`` of one layer call over (t new, s resident)."""
    d, hd = dims["hidden"], dims["head_dim"]
    h, kv = dims["heads"], dims["kv_heads"]
    matmul = 2 * t * (d * h * hd * 2 + d * kv * hd * 2 + 3 * d * dims["ffn"])
    keys_seen = t * s + t * (t + 1) // 2
    attention = 2 * 2 * h * hd * keys_seen
    kv_tok = 2 * kv * hd * dims["kv_bytes"]
    nbytes = (layer_params(dims) * dims["weight_bytes"] + s * kv_tok
              + t * kv_tok + 2 * t * d * dims["act_bytes"])
    return {"flops": matmul + attention, "bytes": nbytes}


def ideal_seconds(t: int, s: int, dims: dict, peaks: dict) -> float:
    """The larger of FLOPs over the bf16 peak and bytes over HBM
    bandwidth: the least time the chip could take for this call."""
    w = work(t, s, dims)
    return max(w["flops"] / peaks["bf16_flops_per_s"],
               w["bytes"] / peaks["hbm_bytes_per_s"])


def head_flops(dims: dict, vocab: int, rows: int = 1) -> int:
    """FLOPs of the output head over ``rows`` positions."""
    return 2 * rows * dims["hidden"] * vocab
