"""GPipe-style pipeline parallelism over a "pipe" mesh axis (shard_map +
ppermute).

Layers are split into n_stages contiguous groups; stage s lives on pipe
shard s (params stacked (n_stages, L/S, ...), dim0 sharded over "pipe").
Microbatches flow through the classic GPipe schedule: at tick t, stage s
processes microbatch (t - s); inter-stage activations move with ONE
collective_permute per tick; bubble fraction = (S-1)/(M+S-1).

This is the optional PP feature for depth-dominated models where TP runs
out of fast links: it composes with the data axis (mesh ("pipe","data")) and
backpropagates through ppermute, so jax.grad of a pipelined loss just works
(GPipe = synchronous PP; no weight staleness).

``pipeline_forward`` pipelines any per-layer body of signature
body(layer_params, x) -> x, e.g. the dense block.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

__all__ = ["stack_stages", "pipeline_forward"]


def stack_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L//n_stages, ...)."""
    def reshape(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return jax.tree.map(reshape, stacked_params)


def pipeline_forward(body, staged_params, x, mesh, *, n_microbatches: int,
                     pipe_axis: str = "pipe"):
    """Run x (B, ...) through all stages with the GPipe schedule.

    body(layer_params, x_mb) -> x_mb (applied L//S times per stage via an
    inner scan). B must be divisible by n_microbatches. Returns (B, ...).
    """
    S = mesh.shape[pipe_axis]
    M = n_microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M
    xs = x.reshape((M, mb) + x.shape[1:])

    def stage_apply(sp, x_mb):
        def scan_body(h, lp):
            return body(lp, h), None

        out, _ = jax.lax.scan(scan_body, x_mb, sp)
        return out

    other_axes = tuple(a for a in mesh.axis_names if a != pipe_axis)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(pipe_axis), P()), out_specs=P(),
             check_vma=False)
    def run(stage_params, xs_rep):
        sid = jax.lax.axis_index(pipe_axis)
        sp = jax.tree.map(lambda p: p[0], stage_params)  # my stage's layers
        zero_mb = jnp.zeros_like(xs_rep[0])
        outputs0 = jnp.zeros_like(xs_rep)

        def tick(t, carry):
            outputs, inflight = carry
            in_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(sid == 0, xs_rep[in_idx], inflight)
            y = stage_apply(sp, x_in)
            # hand y to the next stage (ring permute; last->0 ignored)
            inflight_next = jax.lax.ppermute(
                y, pipe_axis, [(i, (i + 1) % S) for i in range(S)])
            out_t = t - (S - 1)
            valid = (out_t >= 0) & (out_t < M) & (sid == S - 1)
            out_idx = jnp.clip(out_t, 0, M - 1)
            outputs = jnp.where(
                valid, outputs.at[out_idx].set(y), outputs)
            return outputs, inflight_next

        outputs, _ = jax.lax.fori_loop(0, M + S - 1, tick,
                                       (outputs0, zero_mb))
        # only the last stage holds real outputs; broadcast over the ring
        outputs = jnp.where(sid == S - 1, outputs, 0.0)
        return jax.lax.psum(outputs, pipe_axis)

    out = run(staged_params, xs)
    return out.reshape((B,) + out.shape[2:])
