"""Operations of a decoder-only transformer, from its configuration file.

``matmul_params``: weights that take part in a matrix multiplication per
token: per layer the q/k/v/o projections and the MLP, plus the output
head (the tied embedding table where the head is tied). Norms, biases
and the embedding lookup are left out.

Training (PaLM's model-FLOPs rule, no recomputation counted):
``6 * matmul_params + 12 * L * H * Dh * S`` per token at sequence length S.

Serving, per token at position t (it attends t + 1 keys, causal):
``2 * (layer matmul params) + 4 * L * H * Dh * (t + 1)``, plus the head
``2 * D * V`` for each token whose logits are used.
"""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d, h, kh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    dh, f = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * (h + 2 * kh) * dh + h * dh * d
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * d * f
    return cfg["num_hidden_layers"] * (attn + mlp)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    return layer_matmul_params(cfg) + head_params(cfg)


def attn_flops_per_key(cfg: dict) -> int:
    """Score and value products of one query against one key, all layers."""
    return 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) + 3.0 * attn_flops_per_key(cfg) * seq_len


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt's forward pass, logits of its last token only."""
    p = prompt_len
    return (2.0 * layer_matmul_params(cfg) * p
            + attn_flops_per_key(cfg) * p * (p + 1) / 2
            + 2.0 * head_params(cfg))

