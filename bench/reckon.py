"""FLOPs and bytes a step needs, reckoned from the configuration file's
own numbers (the source's keys), never from the program.

Model FLOPs count each multiply-add as two: the projections, the MLP or
the router and the ``num_experts_per_tok`` experts a token is routed to,
the output head over the model's vocabulary, and attention over the
positions a token attends (``4 * heads * head_dim`` per position and
layer: scores and weighted values).  Embedding lookups, norms and
softmax are left out.

Bytes are the least any implementation must move for a step: every
weight once (bf16), and each lane's live keys and values (never the
cache's ``max_len``).
"""

from __future__ import annotations

import numpy as np

from .stats import Work

BF16 = 2


def _attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd


def _ffn_params(c: dict, routed: bool) -> int:
    d = c["hidden_size"]
    if "num_experts" not in c:
        return 3 * d * c["intermediate_size"]
    experts = c["num_experts_per_tok"] if routed else c["num_experts"]
    return d * c["num_experts"] + experts * 3 * d * c["moe_intermediate_size"]


def matmul_flops_per_token(c: dict) -> int:
    per_layer = _attn_params(c) + _ffn_params(c, routed=True)
    return 2 * (c["num_hidden_layers"] * per_layer
                + c["hidden_size"] * c["vocab_size"])


def attn_flops_per_position(c: dict) -> int:
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"])


def weight_bytes(c: dict) -> int:
    d = c["hidden_size"]
    tables = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * d
    norms = 2 * d + 2 * c["head_dim"]
    per_layer = _attn_params(c) + _ffn_params(c, routed=False) + norms
    return BF16 * (tables + c["num_hidden_layers"] * per_layer + d)


def kv_bytes_per_position(c: dict) -> int:
    return (BF16 * 2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"])


def flops(c: dict, w: Work) -> np.ndarray:
    """Model FLOPs of the work in each interval."""
    return matmul_flops_per_token(c) * w.positions + attn_flops_per_position(c) * w.attended


def least_bytes(c: dict, w: Work) -> np.ndarray:
    """Least bytes each interval, taken as one step, must read: every
    weight once where the model did any work, and the keys and values of
    every position cached on a lane."""
    return np.where(w.positions > 0, weight_bytes(c), 0) + kv_bytes_per_position(c) * w.cached
