"""Quickstart: the paper's algorithms + a tiny model, end to end.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (MRCost, compile_plan, prefix_plan, random_indexing,
                        funnel_write, multisearch, multisearch_plan,
                        HardwareModel, LocalEngine, ReferenceEngine,
                        ShardedEngine, sort_plan)
from repro.configs import get_config
from repro.models import build_model


def paper_primitives():
    print("=== paper primitives (I/O-memory-bound MapReduce, M=64) ===")
    M = 64
    rng = np.random.default_rng(0)

    x = jnp.asarray(rng.integers(0, 10, 5000).astype(np.int32))
    pres = compile_plan(prefix_plan(5000, M, dtype=x.dtype))(x)
    print(f"prefix sums (Lemma 2.2): n=5000  rounds={int(pres.stats.rounds)}  "
          f"communication={int(pres.stats.communication)}  "
          f"(paper: O(log_M N), O(N log_M N))")

    c = MRCost()
    idx = random_indexing(5000, jax.random.PRNGKey(1), M, cost=c)
    print(f"random indexing (Lemma 2.3): rounds={c.rounds}  max leaf "
          f"occupancy={c.max_reducer_io} <= M={M}")

    addrs = jnp.asarray(rng.integers(0, 100, 4096).astype(np.int32))
    vals = jnp.ones(4096, jnp.float32)
    c = MRCost()
    hist = funnel_write(addrs, vals, jnp.zeros(100, jnp.float32),
                        jnp.add, M, cost=c, identity=jnp.float32(0))
    print(f"invisible-funnel Sum-CRCW histogram (Thm 3.2): P=4096 "
          f"rounds={c.rounds}  max fan-in={hist.max_fan_in}")

    q = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    piv = jnp.sort(jnp.asarray(rng.normal(size=512).astype(np.float32)))
    c = MRCost()
    ms = multisearch(q, piv, M, cost=c)
    print(f"multi-search (Thm 4.1): |Q|=4096 |T|=512  rounds={ms.rounds}  "
          f"max congestion={ms.max_congestion}")

    x = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    c = MRCost()
    res = compile_plan(sort_plan(4096, M))(x)
    c.absorb(res.stats)
    assert bool(jnp.all(jnp.diff(res.values) >= 0))
    hw = HardwareModel(chips=256, device_kind="TPU v5 lite")
    print(f"sample sort (§4.3): n=4096  rounds={c.rounds}  "
          f"communication={c.communication}")
    print(f"  cost-model wall time on 256 chips "
          f"(T = t + R*L + C/B): {hw.shuffle_time(c)*1e6:.1f} us")


def engine_backends():
    print("\n=== plan/compile/execute: one plan, three backends ===")
    M = 64
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    key = jax.random.PRNGKey(0)
    for engine in (ReferenceEngine(), LocalEngine(), ShardedEngine()):
        plan = sort_plan(4096, M, align=engine.aligned_nodes)
        res = engine.compile(plan)(x, key=key)
        ok = bool(jnp.all(res.values[1:] >= res.values[:-1]))
        print(f"sort_plan[{engine.name:9s}] rounds={int(res.stats.rounds)}"
              f" (bound {plan.round_bound})  comm="
              f"{int(res.stats.communication)}  dropped="
              f"{int(res.stats.dropped)}  sorted={ok}")
    # compile is cached (same fingerprint -> same executable, no retrace),
    # and batch(B) vmaps the whole round program into one device program
    engine = LocalEngine()
    exe = engine.compile(sort_plan(4096, M))
    assert engine.compile(sort_plan(4096, M)) is exe
    B = 8
    xs = jnp.asarray(rng.normal(size=(B, 4096)).astype(np.float32))
    keys = jax.random.split(key, B)
    outs = exe.batch(B)(xs, keys=keys)
    ok = bool(jnp.all(jnp.diff(outs.values, axis=1) >= 0))
    print(f"exe.batch({B}): {B} sorts in one jitted call  sorted={ok}  "
          f"cache={engine.cache_info()}")

    q = jnp.asarray(rng.normal(size=512).astype(np.float32))
    piv = jnp.sort(jnp.asarray(rng.normal(size=64).astype(np.float32)))
    ms = compile_plan(multisearch_plan(512, 64, 16))(q, piv)
    want = np.searchsorted(np.asarray(piv), np.asarray(q), side="left")
    print(f"multisearch_plan[local] rounds={int(ms.stats.rounds)}  correct="
          f"{bool((np.asarray(ms.buckets) == want).all())}")


def tiny_model():
    print("\n=== tiny LM forward/backward on the same substrate ===")
    cfg = get_config("tinyllama-1.1b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32))),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32))),
    }
    (loss, metrics), grads = jax.value_and_grad(
        model.loss_fn, has_aux=True)(params, batch)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} (reduced)  params={n_params:,}  "
          f"loss={float(loss):.3f}  grads finite="
          f"{all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))}")


if __name__ == "__main__":
    paper_primitives()
    engine_backends()
    tiny_model()
