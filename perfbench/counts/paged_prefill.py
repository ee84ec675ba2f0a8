"""Operations and bytes the paged prefill attention kernel needs for one
chunk of ``c`` query tokens after a cached prefix of ``p`` tokens in one
layer: each query attends the prefix and, causally, the chunk
(``c * p + c * (c + 1) / 2`` query-key pairs); the prefix and chunk keys
and values are read once, the queries read and the output written
once."""
from __future__ import annotations


def flops(cfg: dict, p: int, c: int) -> float:
    pairs = c * p + c * (c + 1) / 2
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def bytes_moved(cfg: dict, p: int, c: int, itemsize: int = 2) -> float:
    kv = 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * (p + c)
    qo = 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * c
    return (kv + qo) * itemsize
