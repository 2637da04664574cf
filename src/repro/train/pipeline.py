"""Pipeline parallelism: pipelined BSP supersteps over a mesh axis.

The paper's §4.1 pipelining insight — feed batch i into the DAG at round i
so every level processes one batch per round — is exactly a GPipe schedule:
layers are partitioned into S stages around the 'pp' mesh axis; microbatches
enter the first stage one per step; activations hand off stage-to-stage with
``lax.ppermute`` (the collective-permute the ICI torus does natively).
After S + n_micro - 1 steps every microbatch has crossed every stage —
the same L + K - 1 round count as Theorem 4.1's query pipeline.

Implementation: SPMD inside shard_map.  Every device runs the same step
loop; device s holds stage s's parameters (params pre-sharded over the pp
axis by the caller via PartitionSpec('pp', ...) on the stacked-stage dim).
The rotating buffer pattern keeps one in-flight activation per device.

``run_pipeline`` is forward-only composable (jax.grad differentiates through
the whole schedule = GPipe's synchronous semantics — per-microbatch grads
accumulate exactly as data parallelism of the unrolled graph).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_body(stage_fn: Callable, axis_name: str):
    """Returns fn(stage_params, microbatches) -> outputs, to be called
    INSIDE shard_map over ``axis_name``.

    stage_params: this device's stage parameters (pytree).
    microbatches: (n_micro, mb, ...) — replicated; stage 0 consumes them.
    outputs: (n_micro, mb, ...) — valid on the LAST stage (replicated back
    by the caller if needed).
    """

    def fn(stage_params, microbatches):
        n_stages = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        n_micro = microbatches.shape[0]
        mb_shape = microbatches.shape[1:]
        total_steps = n_micro + n_stages - 1

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range); others use the
            # activation handed over from stage-1 last step.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            injected = microbatches[mb_idx]
            x_in = jnp.where(stage == 0, injected, buf)
            y = stage_fn(stage_params, x_in)
            # last stage records its result for microbatch (t - S + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, y, outs[out_idx]), out_idx, 0)
            # hand off to the next stage (ring; last->0 ignored)
            nxt = lax.ppermute(y, axis_name,
                               [(i, (i + 1) % n_stages)
                                for i in range(n_stages)])
            return (nxt, outs), None

        buf0 = jnp.zeros(mb_shape, microbatches.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, microbatches.dtype)
        (_, outs), _ = lax.scan(step, (buf0, outs0),
                                jnp.arange(total_steps))
        # broadcast final outputs from the last stage to every device so the
        # caller sees replicated results (one psum against a mask).
        mask = (stage == n_stages - 1).astype(outs.dtype)
        return lax.psum(outs * mask, axis_name)

    return fn


def run_pipeline(stage_fn: Callable, stacked_params: Any,
                 microbatches: jnp.ndarray, mesh: Mesh,
                 axis_name: str = "pod") -> jnp.ndarray:
    """Drive the schedule: ``stacked_params`` leaves have leading dim
    n_stages (sharded over ``axis_name``); microbatches (n_micro, mb, ...)
    replicated.  Returns (n_micro, mb, ...) outputs after all stages."""
    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    body = pipeline_body(stage_fn, axis_name)

    def wrapper(params, mb):
        local = jax.tree_util.tree_map(lambda x: x[0], params)  # this stage
        return body(local, mb)

    return jax.jit(jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False))(stacked_params, microbatches)
