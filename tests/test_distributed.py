"""Multi-device tests for the shard_map primitives (8 fake CPU devices).

Each test runs in a subprocess because jax locks the device count at first
init — the main pytest process stays single-device.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_shuffle_alltoall_roundtrip():
    """Thm 2.1 shuffle over a mesh axis: items land at their shard, FIFO
    order within (sender, receiver) pairs, drops counted."""
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import shuffle_alltoall
    mesh = make_host_mesh((8,), ("x",))
    n_local = 16
    def body(dests, vals):
        out = shuffle_alltoall(dests, vals, "x", capacity=n_local)
        return out.payload, out.valid, out.dropped[None]
    rng = np.random.default_rng(0)
    dests = jnp.asarray(rng.integers(0, 8, (8, n_local)).astype(np.int32))
    vals = jnp.arange(8 * n_local, dtype=jnp.float32).reshape(8, n_local)
    f = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(P("x", None), P("x", None)),
                out_specs=(P("x", None), P("x", None), P("x"))))
    payload, valid, dropped = f(dests, vals)
    assert int(jnp.sum(dropped[0])) == 0
    got = np.sort(np.asarray(payload).ravel()[np.asarray(valid).ravel()])
    np.testing.assert_array_equal(got, np.arange(128.0))
    # delivery correctness: every item is on the shard its dest named
    payload_g = np.asarray(payload).reshape(8, 8, n_local)
    valid_g = np.asarray(valid).reshape(8, 8, n_local)
    dests_g = np.asarray(dests)
    vals_g = np.asarray(vals)
    for recv in range(8):
        expect = np.sort(vals_g[dests_g == recv])
        gotr = np.sort(payload_g[recv][valid_g[recv]])
        np.testing.assert_array_equal(gotr, expect)
    print("OK")
    """)
    assert "OK" in out


def test_sharded_engine_kernel_scatter_multishard():
    """ShardedEngine(shuffle_impl='kernel') at axis size 8: the Pallas
    per-shard scatter — the path check_vma=False un-gates inside shard_map —
    must stay bit-identical to the dense sharded and local engines
    (mailbox, validity, and every stat) under real cross-shard collectives."""
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import LocalEngine, ShardedEngine
    rng = np.random.default_rng(0)
    dense, kernel = ShardedEngine(), ShardedEngine(shuffle_impl="kernel")
    assert kernel.n_shards == 8
    local = LocalEngine()
    V = dense.aligned_nodes(20)
    # 1-D entry sends, ample capacity; then 2-D mailbox sends with overflow
    cases = []
    d1 = jnp.asarray(rng.integers(-1, V, 96).astype(np.int32))
    cases.append((d1, jnp.asarray(rng.normal(size=96).astype(np.float32)), 3))
    d2 = jnp.asarray(rng.integers(-1, V, (V, 4)).astype(np.int32))
    cases.append((d2, jnp.asarray(rng.normal(size=(V, 4))
                                  .astype(np.float32)), 2))
    for dests, payload, cap in cases:
        outs = [e.shuffle(dests, payload, V, cap)
                for e in (dense, kernel, local)]
        (bd, sd), (bk, sk), (bl, sl) = outs
        np.testing.assert_array_equal(np.asarray(bd.payload),
                                      np.asarray(bk.payload))
        np.testing.assert_array_equal(np.asarray(bd.valid),
                                      np.asarray(bk.valid))
        np.testing.assert_array_equal(np.asarray(bl.payload),
                                      np.asarray(bk.payload))
        np.testing.assert_array_equal(np.asarray(bl.valid),
                                      np.asarray(bk.valid))
        for a, b, c in zip(sd, sk, sl):
            assert int(a) == int(b) == int(c), (a, b, c)
    print("OK")
    """)
    assert "OK" in out


def test_funnel_allreduce_matches_psum():
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import funnel_allreduce
    mesh = make_host_mesh((2, 4), ("pod", "data"))
    x = jnp.arange(2 * 4 * 16, dtype=jnp.float32).reshape(8, 16)
    def fun(x):
        return funnel_allreduce(x, "data", "pod", scatter_dim=0)
    def ref(x):
        return jax.lax.psum(jax.lax.psum(x, "data"), "pod")
    spec = P(("pod", "data"), None)
    f1 = jax.jit(jax.shard_map(fun, mesh=mesh, in_specs=(spec,),
                               out_specs=spec))
    f2 = jax.jit(jax.shard_map(ref, mesh=mesh, in_specs=(spec,),
                               out_specs=spec))
    np.testing.assert_allclose(np.asarray(f1(x)), np.asarray(f2(x)),
                               rtol=1e-6)
    print("OK")
    """)
    assert "OK" in out


def test_softmax_merge_flash_decode():
    """Sequence-sharded attention partials merge to the exact softmax —
    the (max, sum-exp) funnel."""
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import AttnPartial, softmax_merge_axis
    mesh = make_host_mesh((8,), ("kv",))
    rng = np.random.default_rng(0)
    T, D = 64, 16
    q = jnp.asarray(rng.normal(size=(D,)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    def local(k_shard, v_shard):
        s = k_shard @ q
        m = jnp.max(s)
        p = jnp.exp(s - m)
        return softmax_merge_axis(
            AttnPartial(m=m, l=jnp.sum(p), o=p @ v_shard), "kv")
    f = jax.jit(jax.shard_map(local, mesh=mesh,
                in_specs=(P("kv", None), P("kv", None)), out_specs=P(None)))
    got = f(k, v)
    w = jax.nn.softmax(k @ q)
    want = w @ v
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    print("OK")
    """)
    assert "OK" in out


def test_sharded_sample_sort():
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import sharded_sample_sort
    mesh = make_host_mesh((8,), ("x",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8 * 64,)).astype(np.float32))
    def body(xs):
        o = sharded_sample_sort(xs, "x")
        return o.values, o.valid, o.dropped[None]
    f = jax.jit(jax.shard_map(body, mesh=mesh,
        in_specs=(P("x"),), out_specs=(P("x"), P("x"), P("x"))))
    out_values, out_valid, out_dropped = f(x)
    class O: pass
    out = O(); out.values, out.valid, out.dropped = out_values, out_valid, out_dropped
    vals = np.asarray(out.values).reshape(8, -1)
    valid = np.asarray(out.valid).reshape(8, -1)
    assert int(np.asarray(out.dropped).sum()) == 0
    collected = np.concatenate([vals[i][valid[i]] for i in range(8)])
    np.testing.assert_allclose(collected, np.sort(np.asarray(x)), rtol=1e-6)
    print("OK")
    """)
    assert "OK" in out


def test_moe_shuffle_matches_einsum():
    """The paper-faithful all_to_all MoE dispatch == the einsum dispatch
    (up to capacity-drop differences, tested with ample capacity)."""
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.launch.mesh import make_host_mesh
    from repro.configs import get_config
    from repro.models import sharding as shmod
    from repro.models.moe import init_moe, apply_moe
    mesh = make_host_mesh((2, 4), ("data", "model"))
    cfg = get_config("kimi-k2-1t-a32b", reduced=True)
    cfg = dataclasses.replace(cfg, capacity_factor=8.0, shared_expert=False)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, 16, cfg.d_model)).astype(np.float32)) * 0.3
    with shmod.use_mesh(mesh):
        y_e = apply_moe(p, dataclasses.replace(cfg, moe_dispatch="einsum"), x)
        y_s = apply_moe(p, dataclasses.replace(cfg, moe_dispatch="shuffle"), x)
        np.testing.assert_allclose(np.asarray(y_e.y), np.asarray(y_s.y),
                                   rtol=2e-3, atol=2e-3)
    print("OK, drop_e=%.3f drop_s=%.3f" % (float(y_e.dropped_frac),
                                           float(y_s.dropped_frac)))
    """)
    assert "OK" in out


def test_compressed_pod_training_close_to_exact():
    """Error-feedback int8 cross-pod gradient funnel trains within tolerance
    of the exact pipeline on the same data."""
    out = run_with_devices("""
    import jax, numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.configs import get_config
    from repro.train import Trainer, TrainConfig
    mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    mk = lambda mode: TrainConfig(arch=cfg, global_batch=8, seq_len=32,
                                  steps=10, log_every=1, warmup_steps=2,
                                  peak_lr=5e-4, seed=0, pod_grad_mode=mode)
    exact = Trainer(mk("auto"), mesh=mesh).train()
    comp = Trainer(mk("compressed"), mesh=mesh).train()
    e = exact["final_loss"]; c = comp["final_loss"]
    assert abs(e - c) / abs(e) < 0.05, (e, c)
    print("OK", e, c)
    """)
    assert "OK" in out


def test_elastic_restart_across_mesh_sizes():
    """Checkpoint on one mesh, resume on a different one (elastic)."""
    out = run_with_devices("""
    import tempfile, jax, numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.configs import get_config
    from repro.train import Trainer, TrainConfig
    from repro.train.elastic import plan_mesh
    cfg = get_config("tinyllama-1.1b", reduced=True)
    d = tempfile.mkdtemp()
    mk = lambda: TrainConfig(arch=cfg, global_batch=8, seq_len=16, steps=6,
                             ckpt_dir=d, ckpt_every=3, log_every=1,
                             warmup_steps=2, seed=1)
    mesh1 = make_host_mesh((1, 8, 1), ("pod", "data", "model"))
    t1 = Trainer(mk(), mesh=mesh1)
    t1.train(steps=3)
    # "lose" half the fleet: resume on 4 devices
    mesh2 = make_host_mesh((1, 2, 2), ("pod", "data", "model"))
    t2 = Trainer(mk(), mesh=mesh2)
    assert t2.maybe_resume() and t2.step == 3
    r2 = t2.train()
    # reference: uninterrupted on the small mesh from scratch is NOT
    # comparable; instead check the resumed run proceeds and loss is finite
    assert np.isfinite(r2["final_loss"])
    print("OK", r2["final_loss"])
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_sequential():
    """GPipe schedule over 4 stages == running the 4 stages sequentially;
    grads flow through the pipelined graph."""
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.train.pipeline import run_pipeline
    mesh = make_host_mesh((4,), ("pod",))
    rng = np.random.default_rng(0)
    n_stages, n_micro, mb, d = 4, 6, 8, 16
    ws = jnp.asarray(rng.normal(size=(n_stages, d, d)).astype(np.float32)) * 0.3
    xs = jnp.asarray(rng.normal(size=(n_micro, mb, d)).astype(np.float32))
    stage_fn = lambda w, x: jnp.tanh(x @ w)
    got = run_pipeline(stage_fn, ws, xs, mesh, axis_name="pod")
    want = xs
    for s in range(n_stages):
        want = jnp.tanh(want @ ws[s])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # gradients flow through the schedule
    def loss(ws):
        return jnp.sum(run_pipeline(stage_fn, ws, xs, mesh, axis_name="pod") ** 2)
    g = jax.grad(loss)(ws)
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.max(jnp.abs(g))) > 0
    print("OK")
    """, n_devices=4)
    assert "OK" in out
