"""A planted reference: ``reference/model.py`` with the last layer's MLP
left out (its output projection and bias read as zero). A configuration
that names it has to read ``correct: false``."""
from perfbench.reference import model
from perfbench.reference.model import make_weights  # noqa: F401 (interface)


def _without_last_mlp(params):
    slot = params["layers"]["slot0"]
    mlp = {k: v.at[-1].set(0) if k in ("wo", "bo") else v
           for k, v in slot["mlp"].items()}
    layers = dict(params["layers"], slot0=dict(slot, mlp=mlp))
    return dict(params, layers=layers)


def sequence_logits(cfg, params, tokens, quant=None):
    return model.sequence_logits(cfg, _without_last_mlp(params), tokens,
                                 quant)


def loss_and_grad(cfg, params, batch, quant=None):
    return model.loss_and_grad(cfg, _without_last_mlp(params), batch, quant)
