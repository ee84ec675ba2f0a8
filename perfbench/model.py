"""What a configuration file names: the program's model config, checked
against every width the file states, and the plain reference."""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import sys

#: the plain references, one module a file, found by the name that a
#: configuration gives under ``"reference"``
REFERENCES = pathlib.Path(__file__).resolve().parent / "reference"

#: Mamba-2 and routing widths a configuration may state (Hugging Face
#: key -> the program's field), compared where the file states them
STATED_IF_GIVEN = {"mamba_d_state": "ssm_state", "mamba_d_head": "ssm_headdim",
                   "mamba_n_heads": "ssm_nheads", "mamba_d_conv": "ssm_conv",
                   "mamba_expand": "ssm_expand",
                   "mamba_chunk_size": "ssm_chunk",
                   "num_experts_per_tok": "top_k"}


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
    want.update({f: cfg[k] for k, f in STATED_IF_GIVEN.items() if k in cfg})
    have = {k: getattr(c, k) for k in want}
    if have != want:
        raise ValueError(f"{cfg['name']}: the program's {cfg['arch']} has "
                         f"{have}, the file states {want}")
    if cfg.get("mamba_n_groups", 1) != 1:
        raise ValueError(f"{cfg['name']}: the file states mamba_n_groups "
                         f"{cfg['mamba_n_groups']}; the program's Mamba-2 "
                         f"shares B and C across all heads (one group)")
    return c


def reference_of(cfg: dict):
    """The plain reference module that the configuration names under
    ``"reference"`` (``model`` where it names none): the file
    ``perfbench/reference/<name>.py``, loaded once a process. Its interface
    is set out in ``reference/model.py``'s docstring."""
    name = cfg.get("reference", "model")
    path = REFERENCES / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{cfg['name']} names the reference "
                                f"{name!r}, and there is no {path}")
    key = f"perfbench.reference.{name}"
    mod = sys.modules.get(key)
    if mod is None or pathlib.Path(mod.__file__).resolve() != path:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]   # as an import that fails leaves nothing
            raise
    return mod
