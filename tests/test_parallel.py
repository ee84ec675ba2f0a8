"""Parallelism: sharding rules produce valid (divisible) specs for every
(arch x shape) cell; ZeRO-1; multi-device semantics via subprocess (8
forced host devices): sharded train step == single-device step, compressed
all-reduce with error feedback, pipeline == sequential."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED, SHAPES, cells, get_config
from repro.launch.mesh import axis_size
from repro.parallel import sharding as sh


class _FakeMesh:
    """Shape-only mesh stand-in (no devices needed for rule checks)."""

    def __init__(self, shape_map):
        self.shape = dict(shape_map)
        self.axis_names = tuple(shape_map)
        self.size = 1
        for v in shape_map.values():
            self.size *= v


MESH16 = _FakeMesh({"data": 16, "model": 16})
MESH512 = _FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("mesh", [MESH16, MESH512], ids=["pod1", "pod2"])
@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_param_specs_divisible_all_archs(arch, mesh):
    """Every param leaf's spec must evenly divide its dims (else the real
    NamedSharding construction would fail in the dry-run)."""
    from repro.models import lm
    c = get_config(arch)
    plan = sh.make_plan(c, mesh, SHAPES["train_4k"])
    aps = lm.init_abstract(c)

    def check(path, leaf):
        spec = sh._param_rule(c, plan, path, tuple(leaf.shape))
        for dim, part in enumerate(spec):
            if part is None:
                continue
            axes = part if isinstance(part, tuple) else (part,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert leaf.shape[dim] % n == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(check, aps)


def test_plan_flags():
    p8 = sh.make_plan(get_config("granite-8b"), MESH16, SHAPES["train_4k"])
    assert p8.tp_heads and not p8.fsdp
    pq = sh.make_plan(get_config("qwen2-0.5b"), MESH16, SHAPES["train_4k"])
    assert not pq.tp_heads
    pl4 = sh.make_plan(get_config("llama4-maverick-400b-a17b"), MESH16,
                       SHAPES["train_4k"])
    assert pl4.fsdp and pl4.ep
    pgm = sh.make_plan(get_config("granite-moe-3b-a800m"), MESH16,
                       SHAPES["train_4k"])
    assert not pgm.ep  # 40 experts don't divide 16
    plong = sh.make_plan(get_config("jamba-v0.1-52b"), MESH16,
                         SHAPES["long_500k"])
    assert plong.seq_axis == "data"  # batch=1 -> sequence-sharded cache


def test_zero1_adds_data_axis(subproc):
    subproc("""
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, SHAPES
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as sh

mesh = make_mesh((2, 2), ("data", "model"))
plan = sh.make_plan(get_config("granite-8b"), mesh, SHAPES["train_4k"])
# model-sharded param -> optimizer state additionally sharded over data
ns = sh.zero1_sharding(plan, NamedSharding(mesh, P(None, "model")), (8, 4))
assert ns.spec == P("data", "model"), ns.spec
# data-sharded param -> the extended ZeRO-1 also uses the free model axis
ns2 = sh.zero1_sharding(plan, NamedSharding(mesh, P("data", None)), (8, 4))
assert ns2.spec == P("data", "model"), ns2.spec
# indivisible dims -> untouched
ns3 = sh.zero1_sharding(plan, NamedSharding(mesh, P()), (7,))
assert ns3.spec == P(None), ns3.spec
print("zero1 OK")
""", n_devices=4)


def test_sharded_train_equals_single_device(subproc):
    """2x2 (data x model) sharded train step == unsharded, same batch."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, SHAPES
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.parallel import sharding as sh
from repro.train.optimizer import OptConfig, opt_init
from repro.train.step import StepConfig, make_train_step
import dataclasses

c = get_config("granite-8b").reduced(n_layers=2, d_model=64, n_heads=4,
                                     n_kv_heads=2, d_ff=128, vocab=512,
                                     d_head=16)
oc = OptConfig(warmup=1, total_steps=10)
params = lm.init(jax.random.key(0), c)
opt = opt_init(oc, params)
toks = jax.random.randint(jax.random.key(1), (8, 32), 0, c.vocab, jnp.int32)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
step = make_train_step(c, oc, StepConfig())

# single device
p1, o1, m1 = jax.jit(step)(params, opt, batch)

# sharded 2x2
mesh = make_mesh((2, 2), ("data", "model"))
plan = sh.make_plan(c, mesh, SHAPES["train_4k"])
psh = sh.param_shardings(c, plan, params)
params_s = jax.device_put(params, psh)
opt_s = jax.device_put(opt, jax.tree.map(lambda _: sh.replicated(plan), opt))
batch_s = jax.device_put(batch, sh.batch_sharding(plan, (8, 32)))
with mesh:
    p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)

assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3, (m1["loss"], m2["loss"])
d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
    a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
assert max(jax.tree.leaves(d)) < 3e-2, max(jax.tree.leaves(d))
print("sharded == single OK")
""", n_devices=4)


def test_compressed_psum_error_feedback(subproc):
    """int8 EF all-reduce: mean error shrinks and EF keeps long-run sum
    unbiased (property: accumulated compressed updates -> true mean)."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.compress import compressed_psum

mesh = make_mesh((8,), ("data",))

@partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
         out_specs=(P("data"), P("data")), check_vma=False)
def sync(g, e):
    out, e2 = compressed_psum(g[0], "data", e[0])
    return out[None], e2[None]

key = jax.random.key(0)
g = jax.random.normal(key, (8, 64), jnp.float32)
true_mean = jnp.mean(g, 0)
e = jnp.zeros((8, 64), jnp.float32)
acc_c = jnp.zeros((64,))
acc_t = jnp.zeros((64,))
for i in range(30):
    gi = g * (1.0 + 0.01 * i)
    out, e = sync(gi, e)
    acc_c = acc_c + out[0]
    acc_t = acc_t + jnp.mean(gi, 0)
rel = float(jnp.linalg.norm(acc_c - acc_t) / jnp.linalg.norm(acc_t))
assert rel < 0.01, rel  # error feedback keeps the accumulated sum honest
print("compressed psum EF OK, rel err", rel)
""", n_devices=8)


def test_dryrun_small_mesh_end_to_end(subproc):
    """The dry-run machinery on a small (2,2) mesh for a reduced arch:
    lower+compile+cost/memory analysis + collective parsing all work."""
    subproc("""
import jax
from repro.configs import get_config, SHAPES
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import lower_cell
import dataclasses

c = get_config("granite-8b").reduced(n_layers=2, d_model=64, n_heads=4,
                                     n_kv_heads=2, d_ff=128, vocab=512,
                                     d_head=16)
mesh = make_mesh((2, 2), ("data", "model"))
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=128, global_batch=8)
rec, compiled = lower_cell(c, shape, mesh, "tiny", metrics_pass=True)
assert rec["cost_analysis"]["flops"] > 0
assert rec["roofline"]["bottleneck"] in ("compute", "memory", "collective")
print("dryrun small mesh OK:", rec["roofline"]["bottleneck"])
""", n_devices=4)
