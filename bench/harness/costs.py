"""Operations and bytes that the work needs, counted from shapes and
lengths: the yardstick of every roofline share and of MFU.  The same work
reads the same, whatever implements it."""
from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def dense_shapes(cfg: dict) -> dict:
    """Per-token matmul parameters, bytes of all weights, and KV bytes per
    cached position, of a dense decoder configuration."""
    D, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or D // H
    F, L = cfg["d_ff"], cfg["n_layers"]
    V = -(-cfg["vocab"] // 128) * 128
    n_mlp = 3 if cfg["act"] in ("swiglu", "geglu") else 2
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + n_mlp * D * F
    bias = (H + 2 * K) * hd if cfg.get("qkv_bias") else 0
    norms = (2 if cfg["norm"] == "layernorm" else 1) * D
    item = _ITEM[cfg["param_dtype"]]
    matmul = L * per_layer + D * V  # the output head
    weight_bytes = (L * (per_layer + bias) + D * V) * item \
        + (2 * L + 1) * norms * 4
    return {
        "matmul_params": matmul,
        "params": L * (per_layer + bias + 2 * norms) + norms
        + D * V * (1 if cfg.get("tie_embeddings") else 2),
        "weight_bytes": weight_bytes,
        "embed_row_bytes": D * item,
        "kv_bytes_per_pos": 2 * L * K * hd * item,
        "attn_flops_per_pos": 4 * L * H * hd,
    }


def decode_call(shapes: dict, positions: list[int]) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode-program call that advances one
    token in each slot of ``positions``, each slot attending over that
    many live positions: every weight read once, each token's embedding
    row, and the live KV of those slots read, the new rows written."""
    n = len(positions)
    live = sum(positions)
    flops = 2.0 * shapes["matmul_params"] * n \
        + shapes["attn_flops_per_pos"] * live
    nbytes = shapes["weight_bytes"] + n * shapes["embed_row_bytes"] \
        + (live + n) * shapes["kv_bytes_per_pos"]
    return flops, nbytes
