"""Training runtime: BSP-superstep loop with fault tolerance.

One pjit'd ``train_step`` is one BSP superstep (Thm 3.1): local layer
compute, then the collective exchange.  Gradient reduction follows the
two-level invisible funnel (Thm 3.2 with f=+):

  pod_grad_mode='auto'        GSPMD chooses (reduce-scatter over 'data' is
                              implied by the FSDP output shardings; psum over
                              'pod' inserted by autodiff).  Default.
  pod_grad_mode='compressed'  the cross-pod hop runs through the explicit
                              error-feedback int8 funnel (shard_map manual
                              over 'pod'), cutting the C/B term 4x.

Fault tolerance:
  * async step-atomic checkpoints every ``ckpt_every`` steps;
  * automatic resume from the latest checkpoint (topology-agnostic);
  * batches are a pure function of step — restart-exact data order;
  * a simulated-failure test (tests/test_fault_tolerance.py) kills the loop
    mid-run and verifies bit-exact continuation.

Straggler note (DESIGN.md §5): the per-round I/O bound M caps any reducer's
critical path by construction; on real pods the synchronous collective is
the straggler barrier and mitigation is checkpoint-restart off the slow
host, plus the serving engine's bounded-admission queues.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import build_model
from ..models import sharding as shmod
from ..optim import make_optimizer
from ..optim.api import state_shardings
from ..optim.schedule import warmup_cosine
from ..optim import compress
from ..data import make_pipeline
from . import checkpoint as ckpt


@dataclasses.dataclass
class TrainConfig:
    arch: ArchConfig
    global_batch: int = 8
    seq_len: int = 128
    steps: int = 100
    peak_lr: float = 3e-4
    warmup_steps: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    seed: int = 0
    pod_grad_mode: str = "auto"        # auto | compressed
    log_every: int = 10


def build_train_step(tc: TrainConfig, model, opt, mesh: Mesh):
    cfg = tc.arch

    def lr_at(step):
        return warmup_cosine(step, peak_lr=tc.peak_lr,
                             warmup_steps=tc.warmup_steps,
                             total_steps=max(tc.steps, 2 * tc.warmup_steps))

    if tc.pod_grad_mode == "compressed" and "pod" in mesh.axis_names:
        n_pod = mesh.shape["pod"]

        def train_step(params, opt_state, ef_state, batch):
            # Pod-stacked formulation: split the global batch into its pod
            # shards along the batch dim, compute per-pod grads with vmap,
            # then run the cross-pod funnel hop as the error-feedback int8
            # compressed mean over the stacked dim (the GSPMD-visible image
            # of the manual-over-'pod' psum; per-pod residuals included).
            pod_batch = jax.tree_util.tree_map(
                lambda x: x.reshape((n_pod, x.shape[0] // n_pod)
                                    + x.shape[1:]), batch)

            def pod_grads(b):
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, b)
                return loss, grads

            loss_p, grads_p = jax.vmap(pod_grads)(pod_batch)
            grads, ef_state = compress.tree_stacked_compressed_mean(
                grads_p, ef_state)
            loss = jnp.mean(loss_p)
            new_params, new_state = opt.update(
                grads, opt_state, params, lr_at(opt_state[0]))
            return new_params, new_state, ef_state, loss
        return train_step

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        new_params, new_state = opt.update(grads, opt_state, params,
                                           lr_at(opt_state[0]))
        return new_params, new_state, loss
    return train_step


class Trainer:
    def __init__(self, tc: TrainConfig, mesh: Optional[Mesh] = None):
        self.tc = tc
        self.mesh = mesh
        self.model = build_model(tc.arch)
        self.opt = make_optimizer(tc.arch)
        self.pipeline = make_pipeline(tc.arch, tc.global_batch, tc.seq_len,
                                      seed=tc.seed)
        self.saver = ckpt.AsyncSaver()
        self.step = 0
        self.history: list = []

        with shmod.use_mesh(mesh):
            key = jax.random.PRNGKey(tc.seed)
            self.params = self.model.init(key)
            self.opt_state = self.opt.init(self.params)
            if mesh is not None:
                p_specs = shmod.tree_param_specs(self.params)
                p_sh = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), p_specs)
                self.params = jax.tree_util.tree_map(
                    lambda x, s: jax.device_put(x, s), self.params, p_sh)
                o_sh = state_shardings(self.opt, p_specs, self.params, mesh)
                self.opt_state = jax.tree_util.tree_map(
                    lambda x, s: jax.device_put(x, s), self.opt_state, o_sh,
                    is_leaf=lambda x: isinstance(x, jnp.ndarray))
            self.ef_state = (compress.ef_init(self.params,
                                              n_pod=mesh.shape["pod"])
                             if tc.pod_grad_mode == "compressed"
                             and mesh is not None
                             and "pod" in mesh.axis_names else None)
            step_fn = build_train_step(tc, self.model, self.opt,
                                       mesh if mesh is not None else
                                       _dummy_mesh())
            self._jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    def maybe_resume(self) -> bool:
        tc = self.tc
        if not tc.ckpt_dir:
            return False
        last = ckpt.latest_step(tc.ckpt_dir)
        if last is None:
            return False
        tree = {"params": self.params, "opt_state": self.opt_state}
        restored, meta = ckpt.restore(tc.ckpt_dir, last, tree)
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.step = int(meta["step"])
        return True

    def train(self, steps: Optional[int] = None) -> Dict[str, Any]:
        tc = self.tc
        steps = steps if steps is not None else tc.steps
        t0 = time.time()
        with shmod.use_mesh(self.mesh):
            while self.step < steps:
                batch = {k: jnp.asarray(v) for k, v in
                         self.pipeline.batch_at(self.step).items()}
                if self.ef_state is not None:
                    (self.params, self.opt_state, self.ef_state,
                     loss) = self._jit_step(self.params, self.opt_state,
                                            self.ef_state, batch)
                else:
                    self.params, self.opt_state, loss = self._jit_step(
                        self.params, self.opt_state, batch)
                self.step += 1
                if self.step % tc.log_every == 0 or self.step == steps:
                    self.history.append((self.step, float(loss)))
                if tc.ckpt_dir and self.step % tc.ckpt_every == 0:
                    self.saver.save_async(
                        tc.ckpt_dir, self.step,
                        {"params": self.params, "opt_state": self.opt_state},
                        extra_meta={"arch": tc.arch.name, "seed": tc.seed})
        self.saver.wait()
        return {"history": self.history, "final_loss": self.history[-1][1]
                if self.history else None,
                "wall_s": time.time() - t0}


def _dummy_mesh():
    return jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
