"""Operation and byte counts of a decoder LM, from its configuration alone.

These are the benchmark's own arithmetic: a model-FLOP count follows the
usual convention (2 FLOPs per multiply-add of every matmul weight a token
passes through, the output head included and the embedding gather not,
plus the causal attention score and value products), and training counts
three times the forward pass, with nothing recomputed counted.
"""
from __future__ import annotations

from typing import Dict


def head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layer_matmul_params(m: Dict) -> int:
    """Matmul weights of one attention + dense-MLP block."""
    d, hd = m["d_model"], head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = d * m["d_ff"] * (3 if m.get("mlp_gated", True) else 2)
    return attn + mlp


def matmul_params(m: Dict) -> int:
    """Matmul weights a token passes through: every block and the head."""
    return m["n_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab_size"]


def param_count(m: Dict) -> int:
    """Every parameter of the model as the program holds it: blocks with
    their norms and biases, the embedding, the final norm and an untied
    head."""
    d, hd = m["d_model"], head_dim(m)
    per_layer = layer_matmul_params(m) + 2 * d
    if m.get("qkv_bias"):
        per_layer += (m["n_heads"] + 2 * m["n_kv_heads"]) * hd
    return (m["n_layers"] * per_layer + 2 * m["vocab_size"] * d + d)


def attn_flops_fwd(m: Dict, n_keys: float) -> float:
    """Forward attention FLOPs of one query token over ``n_keys`` keys:
    the score and the value products of every head of every layer."""
    return 4.0 * m["n_layers"] * m["n_heads"] * head_dim(m) * n_keys


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token at causal ``seq_len``."""
    mean_keys = (seq_len + 1) / 2.0
    return 3.0 * (2.0 * matmul_params(m) + attn_flops_fwd(m, mean_keys))


def fwd_flops(m: Dict, n_tokens: float, key_sum: float) -> float:
    """Forward FLOPs of ``n_tokens`` tokens whose queries attend to
    ``key_sum`` keys in all (served prompts and decoded tokens)."""
    return 2.0 * matmul_params(m) * n_tokens + attn_flops_fwd(m, key_sum)
