"""The program's model config for a configuration file, checked against
every width the file states."""
from __future__ import annotations

import dataclasses


def model_config(cfg: dict):
    from repro.configs import get_config
    c = dataclasses.replace(get_config(cfg["arch"]),
                            n_layers=cfg["num_hidden_layers"])
    want = {"d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_head": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "rope_theta": float(cfg["rope_theta"]),
            "tie_embeddings": cfg["tie_word_embeddings"], "dtype": cfg["dtype"]}
    have = {k: getattr(c, k) for k in want}
    if have != want:
        raise ValueError(f"{cfg['name']}: the program's {cfg['arch']} has "
                         f"{have}, the file states {want}")
    return c
