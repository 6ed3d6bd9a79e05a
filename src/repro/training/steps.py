"""Train step builder: loss -> grad -> clip -> optimizer under one jit with
explicit in/out shardings on the production mesh.

Two gradient-sync modes:
  "gspmd"     -- batch sharded over (pod, data); XLA inserts the gradient
                 all-reduce (baseline; lets the compiler overlap).
  "hierarchical" -- grads synced explicitly in shard_map with fp32 intra-pod
                 reduce + compressed (int8/bf16) cross-pod reduce
                 (parallel.collectives) -- the DCN-traffic optimization.

Gradient accumulation (microbatching) runs as a lax.scan over microbatches
inside the same jit.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import model as model_lib
from repro.models.params import param_specs
from repro.parallel.collectives import hierarchical_grad_sync
from repro.parallel.sharding import batch_spec, data_axes

__all__ = ["TrainState", "make_train_step", "state_shardings"]


@jax.tree_util.register_pytree_node_class
@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray
    rng: jnp.ndarray

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step, self.rng), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def state_shardings(cfg, mesh: Mesh, optimizer, abstract_params):
    """NamedSharding tree for TrainState (opt state mirrors params)."""
    pspecs = param_specs(cfg, mesh)
    ns = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    opt_abstract = jax.eval_shape(optimizer.init, abstract_params)
    # opt state is a dict of params-shaped trees -> reuse param shardings
    opt_ns = {k: ns for k in opt_abstract.keys()}
    rep = NamedSharding(mesh, P())
    return TrainState(params=ns, opt_state=opt_ns, step=rep, rng=rep)


def make_train_step(cfg, mesh: Optional[Mesh], optimizer, *,
                    grad_sync: str = "gspmd", compress: str = "int8",
                    accum_steps: int = 1,
                    loss_fn: Optional[Callable] = None):
    """Returns step(state, batch) -> (state, metrics), jit-able with explicit
    shardings when mesh is not None."""
    loss_fn = loss_fn or (lambda p, b: model_lib.loss_fn(p, cfg, b, mesh))

    def compute_grads(params, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

        def micro(carry, mb):
            acc, = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc,), (loss, metrics)

        mbs = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                + x.shape[1:]), batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (acc,), (losses, metricss) = jax.lax.scan(micro, (zeros,), mbs)
        grads = jax.tree.map(lambda g: g / accum_steps, acc)
        metrics = jax.tree.map(lambda m: m.mean(), metricss)
        return losses.mean(), metrics, grads

    def step_fn(state: TrainState, batch):
        rng, step_rng = jax.random.split(state.rng)
        loss, metrics, grads = compute_grads(state.params, batch)
        new_params, new_opt, stats = optimizer.update(
            grads, state.opt_state, state.params, state.step,
            loss_fn=loss_fn, batch=batch, rng=step_rng)
        metrics = dict(metrics, loss=loss, **stats)
        return TrainState(new_params, new_opt, state.step + 1, rng), metrics

    # Shardings for state/batch are supplied by the caller at .lower() /
    # first-call time (dryrun passes NamedShardings explicitly); GSPMD
    # inserts the gradient all-reduce from the batch sharding.
    return jax.jit(step_fn, donate_argnums=(0,))


def make_shard_map_train_step(cfg, mesh: Mesh, optimizer, *,
                              compress: str = "int8",
                              loss_fn: Optional[Callable] = None):
    """Explicit-collective trainer: per-device grads + hierarchical
    compressed sync (parallel.collectives). Params/opt replicated across
    data axes inside the shard_map (TP sharding stays via GSPMD on the
    inner jit-free math).

    Used by the cross-pod-compression dry-run variant and the distributed
    tests; the GSPMD step remains the production default.
    """
    from jax import shard_map

    loss_fn = loss_fn or (lambda p, b: model_lib.loss_fn(p, cfg, b, None))
    axes = data_axes(mesh)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    dname = "data"

    def local_step(params, opt_state, step, rng, batch):
        rng, step_rng, qkey = jax.random.split(rng, 3)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads = hierarchical_grad_sync(grads, data_axis=dname,
                                       pod_axis=pod_axis, key=qkey,
                                       method=compress)
        loss = jax.lax.pmean(loss, dname)
        if pod_axis:
            loss = jax.lax.pmean(loss, pod_axis)
        new_params, new_opt, stats = optimizer.update(
            grads, opt_state, params, step,
            loss_fn=loss_fn, batch=batch, rng=step_rng)
        return new_params, new_opt, step + 1, rng, loss

    bspec = P(axes)
    rep = P()
    smapped = shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, rep, rep, bspec),
        out_specs=(rep, rep, rep, rep, rep),
        check_vma=False)

    def step_fn(state: TrainState, batch):
        p, o, s, r, loss = smapped(state.params, state.opt_state, state.step,
                                   state.rng, batch)
        return TrainState(p, o, s, r), {"loss": loss}

    return jax.jit(step_fn, donate_argnums=(0,))
