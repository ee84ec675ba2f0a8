"""Compile-only memory of each cell's programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python perfbench/fit.py [--cell <name>] ...

No chip is used: the programs are compiled for ``v5e:2x2`` from shapes
alone, and ``memory_analysis()`` gives each program's argument, output and
temporary bytes on one chip. Training cells compile their step; serve cells compile the widest prefill
and decode programs of the mix, with the weights and the paged pool as
arguments. Prints one JSON line per program.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}


def _shaped(tree, sharding):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def fit_train(cfg, mix, topo) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench.model import model_config
    from repro.models import lm
    from repro.train.optimizer import OptConfig, opt_init
    from repro.train.step import StepConfig, make_train_step
    c = model_config(cfg)
    oc = OptConfig(**mix["optimizer"])
    sc = StepConfig(microbatches=mix.get("microbatches", 1), remat=mix["remat"])
    params = lm.init_abstract(c)
    opt = jax.eval_shape(lambda p: opt_init(oc, p), params)
    gb, seq = mix["global_batch"], mix["seq_len"]
    tok = jax.ShapeDtypeStruct((gb, seq), jnp.int32)
    one = SingleDeviceSharding(topo.devices[0])
    args = (_shaped(params, one), _shaped(opt, one),
            {"tokens": _shaped(tok, one), "labels": _shaped(tok, one)})
    step = jax.jit(make_train_step(c, oc, sc), donate_argnums=(0, 1))
    return [("train_step", step.lower(*args).compile())]


def fit_serve(cfg, mix, topo) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench.drivers.serve import decode_widths
    from perfbench.model import model_config
    from repro.models import lm
    from repro.serve.cache import abstract_caches
    c = model_config(cfg)
    eng = mix["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    n, bs, ml, ch = (eng["n_slots"], eng["block_size"], eng["max_len"],
                     eng["chunk_tokens"])
    params = lm.init_abstract(c)
    (shapes, _), _ = abstract_caches(c, n, ml, params)
    n_blocks = 1 + n * (ml // bs)
    pool = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (a.shape[0], n_blocks, bs) + a.shape[3:], a.dtype), shapes)
    p_args = _shaped(params, one)
    pool_args = _shaped(pool, one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    npre = (mix["prompt"]["max"] - ch) // bs

    def prefill_hit(params, caches, tokens, last, pre_blocks):
        logits, rows, _ = lm.prefill(
            c, params, tokens, impl="repeat", last_pos=last,
            paged_prefix=caches, paged_tables=pre_blocks, pos_offset=npre * bs,
            paged_impl="pallas")
        return jnp.argmax(logits[:, -1], -1), rows

    nb = decode_widths(mix)[-1]

    def decode(params, tok, caches, pos, tables):
        logits, caches = lm.decode_step(
            c, params, tok[:, None], caches, pos, impl="grouped",
            block_tables=tables, n_kv_blocks=nb, paged_impl="pallas")
        return jnp.argmax(logits[:, -1], -1), caches

    out = [(f"prefill_chunk{ch}_depth{npre}", jax.jit(prefill_hit).lower(
        p_args, pool_args, i32(n, ch), i32(n), i32(n, npre)).compile())]
    out.append((f"decode_width{nb}", jax.jit(decode, donate_argnums=(2,)).lower(
        p_args, i32(n), pool_args, i32(n), i32(n, ml // bs)).compile()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="append")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    from perfbench.run import cell_files
    for w in bench["workloads"]:
        if args.cell and w["name"] not in args.cell:
            continue
        f = cell_files(bench, w["name"])
        fit = fit_train if f["mix"]["driver"] == "train" else fit_serve
        for name, compiled in fit(f["config"], f["mix"], topo):
            row = {"cell": w["name"], "program": name, **_mem(compiled)}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
