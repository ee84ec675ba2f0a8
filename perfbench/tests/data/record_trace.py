"""Record the small TPU trace that ``test_trace.py`` reduces.

    python perfbench/tests/data/record_trace.py OUT_DIR

Runs, under the profiler, a few host-annotated calls of a jitted matmul
and of the repo's two paged-attention Pallas kernels at tiny shapes, then
copies the ``.xplane.pb`` to ``OUT_DIR/small.xplane.pb`` and prints every
plane and line of it with a few event names, so a reader can see how the
device planes, the kernels and the host spans are named. Needs a TPU.
"""
import glob
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(out_dir: str) -> int:
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.prefill_attention import paged_prefill_attention
    assert jax.devices()[0].platform == "tpu", jax.devices()
    rng = np.random.default_rng(0)
    h, kh, dh, bs, nb, b = 8, 2, 128, 16, 8, 4
    n_blocks = 1 + b * nb
    kp = jnp.asarray(rng.normal(size=(n_blocks, bs, kh, dh)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(n_blocks, bs, kh, dh)), jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(b * nb).reshape(b, nb), jnp.int32)
    lengths = jnp.asarray([128, 100, 64, 17], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.bfloat16)
    qs = jnp.asarray(rng.normal(size=(b, 64, h, dh)), jnp.bfloat16)
    ks = jnp.asarray(rng.normal(size=(b, 64, kh, dh)), jnp.bfloat16)
    dec = jax.jit(paged_decode_attention)
    pre = jax.jit(lambda *a: paged_prefill_attention(*a))
    mm = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    for _ in range(2):   # compile outside the trace
        dec(q, kp, vp, tables, lengths).block_until_ready()
        pre(qs, ks, ks, kp, vp, tables[:, :4]).block_until_ready()
        mm(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.decode"):
                dec(q, kp, vp, tables, lengths).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                pre(qs, ks, ks, kp, vp, tables[:, :4]).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.matmul"):
                mm(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                sum(range(200_000))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("bytes", os.path.getsize(dst))
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(dst).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print("  LINE", repr(line.name), len(evs), names[:12])
            for e in evs[:2]:
                print("    ev", e.name, e.start_ns, e.duration_ns,
                      {k: str(v)[:60] for k, v in e.stats})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
