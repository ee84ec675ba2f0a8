"""Useful operations of the hybrid model's prefill, from the
configuration file (Granite 4.0-H's keys), per real prompt token.

- Mamba layers: in_proj and out_proj, the depthwise conv, and the SSD
  math of each 256-token chunk the engine feeds (chunk ``c``): the
  causal ``C B^T`` and weighted input sums over ``c (c + 1) / 2`` pairs
  and, per token, the state's output and update.
- Attention layers: q/k/v/o projections, and scores and values over the
  prefix and, causally, the token itself.
- Every layer: the router over all experts and the shared expert.
- Routed experts: ``routed_flops`` of the choices the program counted
  (its ``moe_local_prefill``), not what the router would send on average.
- The head's logits for each prompt's last token.

Two FLOPs a multiply-add; norms, gates and activations left out.
"""
from __future__ import annotations

from perfbench.counts.hybrid_decode import layer_kinds, padded_vocab

CHUNK = 256


def _per_token(cfg: dict) -> tuple:
    d = cfg["hidden_size"]
    nh, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    di, ns, k = nh * hp, cfg["mamba_d_state"], cfg["mamba_d_conv"]
    h, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    mamba = 2 * d * (2 * di + 2 * ns + nh) + 2 * k * (di + 2 * ns) \
        + 2 * di * d + 4 * nh * hp * ns
    attn = 2 * d * (h + 2 * kh) * dh + 2 * h * dh * d
    every = 2 * d * cfg["published"]["num_local_experts"] \
        + 6 * d * cfg["shared_intermediate_size"]
    return mamba, attn, every


def prompt_flops(cfg: dict, prompt_len: int, chunk: int = CHUNK) -> float:
    """A whole prompt's prefill but for the routed experts."""
    kinds = layer_kinds(cfg)
    n_mamba = sum(k == "mamba" for k in kinds)
    n_attn = len(kinds) - n_mamba
    mamba, attn, every = _per_token(cfg)
    nh, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    p = prompt_len
    pairs = sum(c * (c + 1) / 2 for c in
                (min(chunk, p - s) for s in range(0, p, chunk)))
    ssd = 2 * pairs * (cfg["mamba_d_state"] + nh * hp)
    keys = p * (p + 1) / 2
    return (n_mamba * (mamba * p + ssd)
            + n_attn * (attn * p + 4 * cfg["num_attention_heads"]
                        * cfg["head_dim"] * keys)
            + len(kinds) * every * p
            + 2 * cfg["hidden_size"] * padded_vocab(cfg))


def routed_flops(cfg: dict, choices: int) -> float:
    """``choices`` token-expert pairs through a SwiGLU expert."""
    return 6.0 * cfg["hidden_size"] * cfg["intermediate_size"] * choices
