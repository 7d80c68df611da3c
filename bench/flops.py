"""Model operations per token, from a configuration's shapes, and the
chip's peaks.

Training FLOPs per token are those the forward and backward passes
require (3 x the forward's), with nothing counted for recomputation
under rematerialization.  Each matrix multiplication of an ``m x n``
weight costs ``2 m n`` per token forward.  Attention's two products
(``Q K^T`` and ``P V``) cost ``2 * 2 * ctx * n_heads * head_dim`` per
token forward, with ``ctx`` the keys a causal query sees on average:
``(seq_len + 1) / 2``.  The output head counts at the width the program
computes its logits (``padded_vocab_size`` where the file gives one).
Norms, biases, rotary embedding, softmax and the optimizer are left out:
under 1% here.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights multiplied per token: every layer's projections and the
    output head (the embedding is a lookup)."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // heads)
    attn = d * hd * (heads + 2 * kv) + heads * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    vocab = cfg.get("padded_vocab_size", cfg["vocab_size"])
    return cfg["num_hidden_layers"] * (attn + mlp) + vocab * d


def attention_flops_fwd(cfg: Dict[str, Any], seq_len: int) -> float:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // heads)
    ctx = (seq_len + 1) / 2
    return cfg["num_hidden_layers"] * 2 * 2 * ctx * heads * hd


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    return 3 * (2 * matmul_params(cfg) + attention_flops_fwd(cfg, seq_len))


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
