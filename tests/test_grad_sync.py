"""Gradient-sync correctness (ISSUE 6): bucketed/overlapped dp sync vs
naive per-leaf psum, compressed_psum error-feedback convergence, ZeRO-2
vs replicated-grad train-state equality, and the sharding/recompile
audit that caught the dp-scaling collapse."""
import numpy as np
import pytest

from repro.parallel.grad_sync import GradSyncConfig, default_sync

# ---------------------------------------------------------------------------
# Config + bucketing (single device, no subprocess)
# ---------------------------------------------------------------------------


def test_grad_sync_config_rejects_unknown_mode():
    with pytest.raises(ValueError, match="grad_sync mode"):
        GradSyncConfig(mode="fp16")


def test_default_sync_disables_overlap_on_cpu():
    # overlap pays only where collectives run async; the test host is CPU
    s = default_sync("int8")
    assert s.mode == "int8" and s.overlap is False


def test_flatten_buckets_round_trip():
    import jax.numpy as jnp
    from repro.parallel.grad_sync import (flatten_buckets, n_buckets,
                                          unflatten_buckets)
    tree = {"a": jnp.arange(7, dtype=jnp.float32).reshape(7),
            "b": jnp.ones((3, 5), jnp.bfloat16),
            "c": jnp.zeros((), jnp.float32)}
    buckets, meta = flatten_buckets(tree, bucket_elems=6)
    assert len(buckets) == n_buckets(tree, 6) == 4   # 23 elems / 6
    assert all(b.shape == (6,) for b in buckets)
    back = unflatten_buckets(buckets, meta)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(tree[k], np.float32))


# ---------------------------------------------------------------------------
# Multi-device equivalences (forced host platform, subprocess)
# ---------------------------------------------------------------------------


_DP_PRELUDE = """
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.bench.spec import Placement
from repro.launch.mesh import mesh_for
from repro.models import lm
from repro.data.synthetic import synthetic_tokens
from repro.parallel import sharding as shd, grad_sync as gs
from repro.train.optimizer import OptConfig, opt_init
from repro.train.step import StepConfig, make_train_step

c = get_config("gpt-117m").reduced(d_model=64, n_layers=2, d_ff=128,
                                   vocab=512, n_heads=2, n_kv_heads=2,
                                   d_head=32)
oc = OptConfig(warmup=2, total_steps=100)
params = lm.init(jax.random.key(0), c)
opt_state = opt_init(oc, params)
mesh = mesh_for(Placement.of("dp2"))
plan = shd.make_plan(c, mesh, ShapeConfig("t", 0, 0, "train"))
p_s, o_s, psh, osh, gsh = shd.shard_train_state(plan, params, opt_state, c)
gb, seq, k = 8, 16, 4
toks = jnp.asarray(synthetic_tokens(gb, seq, c.vocab)[:, :seq])
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
pbatch = jax.device_put(batch, {kk: shd.batch_sharding(plan, v.shape)
                                for kk, v in batch.items()})

def run_steps(step, n, with_sync=None):
    p = jax.device_put(jax.tree.map(jnp.copy, p_s), psh)
    o = jax.device_put(jax.tree.map(jnp.copy, o_s), osh)
    s = with_sync
    for _ in range(n):
        if s is not None:
            p, o, s, m = step(p, o, s, pbatch)
        else:
            p, o, m = step(p, o, pbatch)
    return p, m

def maxdiff(a, b):
    ds = jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(
        x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)
    return max(jax.tree.leaves(ds))
"""


def test_bucketed_sync_matches_naive_psum_and_single_device(subproc):
    """Fixed seed, few fp32 steps: the bucketed dp2 step (overlap on AND
    off, tiny buckets forcing multiple) lands on the same params as (a)
    a shard_map step using naive per-leaf psum and (b) the plain
    single-logical-batch GSPMD step."""
    subproc(_DP_PRELUDE + """
from jax.sharding import PartitionSpec as P
from functools import partial

sc = StepConfig(microbatches=k)

# (a) naive per-leaf psum reference, same scan, no buckets
from repro.train.step import make_loss_fn, scan_microbatch_grads
from repro.train.optimizer import opt_update
vg = jax.value_and_grad(make_loss_fn(c, sc), has_aux=True)
axis = plan.dp if len(plan.dp) > 1 else plan.dp[0]
ndev = shd.dp_size(plan)

def naive_local(params, batch):
    g, _, l, ce, aux = scan_microbatch_grads(vg, params, batch, k,
                                             jnp.float32)
    g = gs.naive_psum_sync(g, axis, ndev)
    g = jax.tree.map(lambda x: (x / k).astype(jnp.float32), g)
    return g, jax.lax.pmean(l / k, axis)

smap = jax.shard_map(naive_local, mesh=mesh, in_specs=(P(), P(plan.dp)),
                     out_specs=(P(), P()), check_vma=False)

def naive_step(p, o, batch):
    g, l = smap(p, batch)
    np_, no, info = opt_update(oc, g, o, p)
    return np_, no, {"loss": l, **info}

naive = jax.jit(naive_step, out_shardings=(psh, osh, None),
                donate_argnums=(0, 1))
p_naive, _ = run_steps(naive, 3)

for overlap in (False, True):
    sync = gs.GradSyncConfig(mode="fp32", bucket_mb=0.001, overlap=overlap)
    step = jax.jit(gs.make_dp_train_step(c, oc, sc, plan=plan, sync=sync),
                   out_shardings=(psh, osh, gs.sync_state_sharding(plan),
                                  None),
                   donate_argnums=(0, 1, 2))
    p_b, _ = run_steps(step, 3, with_sync=gs.init_sync_state(
        plan, params, sync))
    d = maxdiff(p_b, p_naive)
    assert d < 2e-3, f"overlap={overlap}: bucketed vs naive diff {d}"
    print("overlap", overlap, "vs naive diff", d)

# (b) the plain single-device-semantics GSPMD step
ref = jax.jit(make_train_step(c, oc, sc), out_shardings=(psh, osh, None),
              donate_argnums=(0, 1))
p_ref, _ = run_steps(ref, 3)
d = maxdiff(p_naive, p_ref)
assert d < 2e-3, f"naive-psum vs gspmd diff {d}"
print("OK")
""", n_devices=2)


def test_zero2_grad_shardings_match_replicated_grads(subproc):
    """ZeRO-2 (dp-sharded grad accumulators) is a layout change, not a
    numeric one: few fp32 steps with grad_shardings=gsh equal the
    replicated-grad (grad_shardings=None) step."""
    subproc(_DP_PRELUDE + """
sc = StepConfig(microbatches=k)
mb = gb // k
mbsh = {"tokens": shd.batch_sharding(plan, (mb, seq)),
        "labels": shd.batch_sharding(plan, (mb, seq))}
z2 = jax.jit(make_train_step(c, oc, sc, grad_shardings=gsh,
                             batch_shardings=mbsh),
             out_shardings=(psh, osh, None), donate_argnums=(0, 1))
rep = jax.jit(make_train_step(c, oc, sc, batch_shardings=mbsh),
              out_shardings=(psh, osh, None), donate_argnums=(0, 1))
p_z2, m_z2 = run_steps(z2, 3)
p_rep, m_rep = run_steps(rep, 3)
d = maxdiff(p_z2, p_rep)
assert d < 1e-5, f"zero2 vs replicated diff {d}"
assert abs(float(m_z2["loss"]) - float(m_rep["loss"])) < 1e-5
# the accumulator really is dp-sharded: at least one gsh leaf names an
# axis its psh twin leaves free
import jax.tree_util as jtu
extra = [g for p, g in zip(jax.tree.leaves(psh), jax.tree.leaves(gsh))
         if p.spec != g.spec]
assert extra, "gsh identical to psh — ZeRO-2 sharded nothing"
print("OK, zero2 shards", len(extra), "leaves further")
""", n_devices=2)


def test_pinned_step_neither_recompiles_nor_reshards(subproc):
    """The collapse regression drill: the pinned+donated dp step keeps
    jit cache size 1 and returns params on exactly the input shardings
    (the unpinned seed step recompiled on call 1 and resharded all
    leaves — scaling_efficiency 0.10)."""
    subproc(_DP_PRELUDE + """
from repro.train.diagnose import audit_shardings
sc = StepConfig(microbatches=k)
sync = gs.GradSyncConfig(mode="fp32", overlap=False)
step = jax.jit(gs.make_dp_train_step(c, oc, sc, plan=plan, sync=sync),
               out_shardings=(psh, osh, gs.sync_state_sharding(plan), None),
               donate_argnums=(0, 1, 2))
p = jax.device_put(jax.tree.map(jnp.copy, p_s), psh)
o = jax.device_put(jax.tree.map(jnp.copy, o_s), osh)
s = gs.init_sync_state(plan, params, sync)
for i in range(3):
    p, o, s, m = step(p, o, s, pbatch)
    assert step._cache_size() == 1, f"recompiled at call {i}"
assert audit_shardings(p, psh) == 0, "outputs left the input placement"
print("OK")
""", n_devices=2)


def test_compressed_psum_error_feedback_converges(subproc):
    """Error feedback keeps the cumulative int8-compressed mean unbiased:
    over repeated reduces of the same gradients, the accumulated
    compressed means approach the accumulated true means (residual
    stays bounded) — the Seide-style convergence property."""
    subproc("""
import jax, jax.numpy as jnp
import numpy as np
from functools import partial
from repro.parallel.compress import compressed_psum
from jax.sharding import PartitionSpec as P
from jax.sharding import Mesh

mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
key = jax.random.key(0)
x = jax.random.normal(key, (4, 256)) * jnp.linspace(0.1, 3.0, 256)

def one_round(x, err):
    out, new_err = compressed_psum(x, "data", err)
    return out, new_err

smap = jax.jit(jax.shard_map(one_round, mesh=mesh,
                             in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data")),
                             check_vma=False))
true_mean = jnp.mean(x, axis=0)
err = jnp.zeros_like(x)
acc = jnp.zeros_like(true_mean)
drifts = []
for t in range(1, 33):
    out, err = smap(x, err)
    acc = acc + out[0]
    drifts.append(float(jnp.max(jnp.abs(acc / t - true_mean))))
# the RUNNING mean drift shrinks as the residual is fed back; without
# error feedback it would plateau at the quantization bin size
assert drifts[-1] < drifts[0] / 4, drifts
assert drifts[-1] < 0.02, drifts[-1]
# residual itself stays bounded by one quantization bin
assert float(jnp.max(jnp.abs(err))) < float(jnp.max(jnp.abs(x))) / 100
print("drift", drifts[0], "->", drifts[-1])
""", n_devices=4)
