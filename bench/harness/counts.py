"""Bytes and operations a decode step needs, from a configuration's own
sizes (the keys of its file under ``bench/configs``).

Bytes follow the paper's floor convention (the program's
``core/floor.py``): every weight once, plus the live KV of every live
sequence at its length.  Operations: two per weight of each matrix
multiplication per token (the output head included, the embedding
lookup not), plus the attention score and value products over the live
context.
"""
from __future__ import annotations

from typing import Dict, Sequence

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def qkv_bias(cfg: Dict) -> bool:
    return bool(cfg.get("attention_bias", cfg.get("bias", False)))


def _layer_matmul_params(cfg: Dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp


def param_count(cfg: Dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = _layer_matmul_params(cfg) + 2 * d
    if qkv_bias(cfg):
        per_layer += (hq + 2 * hkv) * hd
    tables = 1 if cfg["tie_word_embeddings"] else 2
    return (cfg["num_hidden_layers"] * per_layer
            + tables * cfg["vocab_size"] * d + d)


def dtype_bytes(cfg: Dict) -> int:
    return DTYPE_BYTES[cfg["torch_dtype"]]


def weight_bytes(cfg: Dict) -> int:
    return param_count(cfg) * dtype_bytes(cfg)


def kv_bytes_per_token(cfg: Dict) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * dtype_bytes(cfg))


def step_bytes(cfg: Dict, live_lengths: Sequence[int]) -> int:
    """Bytes one decode step streams: weights + live KV of every lane."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(live_lengths)


def step_flops(cfg: Dict, live_lengths: Sequence[int]) -> int:
    """Operations of one decode step over the given live lanes."""
    per_token = 2 * (_layer_matmul_params(cfg) * cfg["num_hidden_layers"]
                     + cfg["vocab_size"] * cfg["hidden_size"])
    attn = (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg))
    return per_token * len(live_lengths) + attn * sum(live_lengths)


def attention_kv_bytes(cfg: Dict, live_lengths: Sequence[int]) -> int:
    """KV bytes the decode attention of one step must read, all layers."""
    return kv_bytes_per_token(cfg) * sum(live_lengths)


def attention_flops(cfg: Dict, live_lengths: Sequence[int]) -> int:
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * sum(live_lengths))


def roofline_s(flops: float, nbytes: float, peaks) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks.flops_bf16, nbytes / peaks.hbm_bytes_per_s)
