"""Training cells: the program's jitted train step driven through
``train.loop.train_loop``, as ``launch.train`` drives it.

Set-up builds one object, the compiled step with its state, from the
benchmark's seeded weights, and drives it through its first three steps
inside one ``train_loop`` call; the same call then runs on as the
measured window until ``--seconds`` have passed, at a step boundary. The
readings for ``correct`` are taken in those first steps:

- each step's loss (the loop's own metrics);
- the norm, leaf by leaf, of the first gradient as the optimizer got it,
  worked out from its first moment after one step (``m / (1 - b1)``);
- the norm, leaf by leaf, of each parameter's change after three steps,
  from the float32 master copy, before step 4 takes it.

After the window the state is freed and the plain reference
(``reference/train.py``, on the loss and gradient of the reference that
the configuration names) repeats the three steps from the same weights
and rows; the gaps are the numbers compared.

The driver runs one chip; a data-parallel mix needs a driver of its own.
"""
from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.model import model_config, reference_of
from perfbench.reference import train as ref_train
from perfbench.reference.model import _paths

#: what this driver calls of the configuration's plain reference
REFERENCE_CALLS = ("make_weights", "loss_and_grad")

#: correctness readings are taken over this many leading steps
N_CHECK = 3


class WindowClosed(Exception):
    pass


def make_rows(cfg: dict, mix: dict, seed: int) -> np.ndarray:
    """The run's token rows: Zipf(1.3) ids modulo the vocabulary, the
    rank-frequency rule of the program's synthetic data, from ``seed``."""
    rng = np.random.default_rng(int(seed))
    n = mix["global_batch"] * mix["row_pool_steps"]
    z = rng.zipf(1.3, size=(n, mix["seq_len"] + 1))
    return (z % cfg["vocab_size"]).astype(np.int32)


def batch_of(rows: np.ndarray, gb: int, step: int) -> dict:
    n_steps = rows.shape[0] // gb
    r = rows[(step % n_steps) * gb:(step % n_steps + 1) * gb]
    return {"tokens": r[:, :-1], "labels": r[:, 1:]}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b.astype(jnp.float32))))


def host_norms(names: list, tree, scale: float = 1.0) -> dict:
    leaves = jax.tree_util.tree_leaves(jax.device_get(_norms(tree)))
    return {n: float(v) * scale for n, v in zip(names, leaves)}


def change_norms(names: list, master, w0: list) -> dict:
    """Per-leaf norm of ``master`` minus the initial weights ``w0`` (host
    copies), one leaf at a time on the device."""
    return {n: float(_diff_norm(m, jnp.asarray(w)))
            for n, m, w in zip(names, jax.tree_util.tree_leaves(master), w0)}


def build(ctx, c, rows, reference):
    """The compiled step, its state (weights from ``reference``) and the
    feed: one object, as the window will use it. Returns (step, params,
    opt_state, data, abstract, oc)."""
    from repro.models import lm
    from repro.train.optimizer import OptConfig, opt_init
    from repro.train.step import StepConfig, make_train_step
    mix = ctx.mix
    if mix.get("dp", 1) != 1:
        raise ValueError(f"the train driver runs one chip; dp={mix['dp']}")
    oc = OptConfig(**mix["optimizer"])
    sc = StepConfig(microbatches=mix.get("microbatches", 1),
                    remat=mix["remat"])
    abstract = lm.init_abstract(c)
    gb = mix["global_batch"]
    params = reference.make_weights(abstract, ctx.seed)
    opt_state = jax.jit(partial(opt_init, oc))(params)
    step = jax.jit(make_train_step(c, oc, sc), donate_argnums=(0, 1))

    def data(i):
        return {k: jnp.asarray(v) for k, v in batch_of(rows, gb, i).items()}

    compiled = step.lower(params, opt_state, data(0)).compile()
    return compiled, params, opt_state, data, abstract, oc


def run(ctx) -> dict:
    from repro.train.loop import LoopConfig, train_loop
    cfg, mix = ctx.cfg, ctx.mix
    reference = reference_of(cfg)
    c = model_config(cfg)
    rows = make_rows(cfg, mix, ctx.seed)
    gb, seq = mix["global_batch"], mix["seq_len"]
    with ctx.span("bench.build"):
        step, params, opt_state, data, abstract, oc = build(ctx, c, rows,
                                                            reference)
        # the initial weights, on the host: what the change is taken from
        names = _paths(abstract)[0]
        w0 = jax.device_get(jax.tree_util.tree_leaves(params))
    readings: dict = {"loss": []}
    calls = {"n": 0}

    def stepper(p, o, b):
        with ctx.span("bench.step"):
            p, o, m = step(p, o, b)
        calls["n"] += 1
        if calls["n"] == 1:
            readings["grad"] = host_norms(names, o["m"],
                                          1.0 / (1.0 - oc.b1))
        if calls["n"] == N_CHECK:
            readings["change"] = change_norms(names, o["master"], w0)
        stepper.last = (p, o)
        return p, o, m

    trace_at = mix.get("trace_skip_steps", 2)
    trace_steps = mix.get("trace_steps", 8)
    times: list = []

    def hook(i, metrics, _dt):
        now = time.perf_counter()
        if i < N_CHECK:
            readings["loss"].append(float(metrics["loss"]))
            if i == N_CHECK - 1:
                times.append(ctx.open_window())
            return
        times.append(now)
        n_win = i - N_CHECK + 1
        if ctx.trace:
            if n_win == trace_at:
                ctx.start_trace()
                times.append(time.perf_counter())
            elif n_win == trace_at + trace_steps:
                ctx.stop_trace()
                raise WindowClosed
        elif now - times[0] >= ctx.seconds:
            raise WindowClosed

    cfg_loop = LoopConfig(total_steps=10 ** 9, ckpt_every=0, log_every=0,
                          seq_len=seq, global_batch=gb)
    try:
        with ctx.span("bench.window"):
            train_loop(stepper, params, opt_state, data, cfg_loop,
                       hooks=[hook])
    except WindowClosed:
        pass
    ctx.close_window()
    if ctx.trace:
        n_win, wall = trace_steps, ctx.trace_window_s
    else:
        n_win, wall = len(times) - 1, times[-1] - times[0]
    ctx.read_memory()
    del params, opt_state, stepper.last
    gc.collect()
    setup_s = ctx.window_start - ctx.t_start
    ctx.notes.update(window_steps=n_win, window_s=wall, losses=readings["loss"])

    t_ref = time.perf_counter()
    batches = [batch_of(rows, gb, i) for i in range(N_CHECK)]
    ref = ref_train.reference_readings(reference.loss_and_grad, cfg, mix,
                                       names, w0, batches)
    ctx.notes["reference_s"] = time.perf_counter() - t_ref
    checks = compare(readings, ref, ctx.limits)
    ctx.notes.update(worst_grad_leaf=worst_leaf_gap(readings["grad"],
                                                    ref["grad"])[1],
                     reference_losses=ref["loss"])
    tokens = n_win * gb * seq
    return {
        "attempted": N_CHECK, "failed": 0,
        "e2e": {"train_step_ms": wall / n_win * 1e3, "setup_s": setup_s},
        "checks": checks,
        "facts": {"tokens": tokens, "steps": n_win, "global_batch": gb,
                  "seq_len": seq, "step_s": wall / n_win},
        "readings": readings, "reference": ref,
        "control_inputs": (names, w0, batches),
    }


def control(ctx, out) -> dict:
    """The control's numbers and the planted faults' at this cell's size,
    each (name, value, limit) against the cell's limits: the reference
    computed from fp8 operands in the program's place, and the reference
    with half of each batch left out (the mean over the rest). A state
    left unchanged reads 1 on the change by construction."""
    names, w0, batches = out["control_inputs"]
    ref = out["reference"]
    gb = ctx.mix["global_batch"]
    loss_and_grad = reference_of(ctx.cfg).loss_and_grad
    fp8 = ref_train.reference_readings(loss_and_grad, ctx.cfg, ctx.mix,
                                       names, w0, batches, quant="fp8")
    half = ref_train.reference_readings(loss_and_grad, ctx.cfg, ctx.mix,
                                        names, w0, batches,
                                        rows=slice(0, gb // 2))
    return {"fp8": compare(fp8, ref, ctx.limits),
            "half_batch": compare(half, ref, ctx.limits)}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """Largest gap between the program's and the reference's norm of a
    leaf, against the larger of that leaf's reference norm and the median
    leaf's; and that leaf's name."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max((abs(prog[n] - ref[n]) / max(ref[n], med), n) for n in names)


def compare(readings: dict, ref: dict, limits: dict) -> list:
    """The numbers compared, each with its limit: (name, value, limit)."""
    loss_gap = max(abs(a - b) for a, b in zip(readings["loss"], ref["loss"]))
    grad_gap, _ = worst_leaf_gap(readings["grad"], ref["grad"])
    gmed = float(np.median(list(ref["grad"].values())))
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: out of the change, by rule
    keep = {n for n, g in ref["grad"].items() if g >= 1e-3 * gmed}
    change_gap, _ = worst_leaf_gap(readings["change"], ref["change"], keep)
    return [("loss_gap", loss_gap, limits["loss_gap"]),
            ("grad_norm_gap", grad_gap, limits["grad_norm_gap"]),
            ("change_norm_gap", change_gap, limits["change_norm_gap"])]
