"""Bytes the hybrid decode program has to move at the least, from the
configuration file (Granite 4.0-H's keys): every weight held here read
once a micro-step (bf16, with the router and the Mamba ``dt_bias``,
``A_log`` and ``D`` in float32, as the program holds them); the conv
(bf16) and SSM (float32) state of every batch row of every Mamba layer,
read and written; and the live KV blocks of the attention layers (bf16
keys and values), each read once."""
from __future__ import annotations

BF16, F32 = 2, 4


def layer_kinds(cfg: dict) -> list:
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def _mamba(cfg: dict) -> tuple:
    nh, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return nh, hp, nh * hp, cfg["mamba_d_state"], cfg["mamba_d_conv"]


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def weight_bytes(cfg: dict) -> int:
    d = cfg["hidden_size"]
    nh, _hp, di, ns, k = _mamba(cfg)
    h, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    e, held = cfg["published"]["num_local_experts"], cfg["num_local_experts"]
    f, fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    conv_ch = di + 2 * ns
    mamba = BF16 * (d * (2 * di + 2 * ns + nh) + (k + 1) * conv_ch + di
                    + di * d) + F32 * 3 * nh
    attn = BF16 * (2 * d * h * dh + 2 * d * kh * dh)
    moe = F32 * d * e + BF16 * (3 * d * (held * f + fs) + d)
    total = BF16 * padded_vocab(cfg) * d + BF16 * d          # table, final norm
    for kind in layer_kinds(cfg):
        total += BF16 * d + moe + (mamba if kind == "mamba" else attn)
    return total


def state_bytes(cfg: dict, rows: int) -> int:
    """Conv and SSM state of ``rows`` batch rows, read and written."""
    nh, hp, di, ns, k = _mamba(cfg)
    row = BF16 * (k - 1) * (di + 2 * ns) + F32 * nh * hp * ns
    n = sum(kind == "mamba" for kind in layer_kinds(cfg))
    return 2 * n * rows * row


def kv_block_bytes(cfg: dict, block_size: int) -> int:
    """One block of keys and values in every attention layer."""
    n = sum(kind == "attention" for kind in layer_kinds(cfg))
    return n * 2 * BF16 * block_size * cfg["num_key_value_heads"] \
        * cfg["head_dim"]


def least_bytes(cfg: dict, rows: int, block_size: int, n_steps: int,
                used_blocks: int) -> int:
    """``n_steps`` micro-steps over ``rows`` rows that attended
    ``used_blocks`` live blocks in all."""
    return (n_steps * (weight_bytes(cfg) + state_bytes(cfg, rows))
            + used_blocks * kv_block_bytes(cfg, block_size))
