"""Expert-parallel MoE via shard_map with LOCAL dispatch (§Perf).

Baseline failure mode (moe.py under GSPMD): tokens are data-sharded, the
(experts, capacity, d) buffer is expert-sharded -- the dispatch scatter
crosses the sharding boundary and XLA lowers it as full-buffer all-reduces
(granite-1b: 4.5e11 B/layer/device of all-reduce wire -> 195 s collective
term).

This implementation keeps tokens on their (pod, data) shard; every model
shard routes ALL of its local tokens but builds buffers ONLY for its own
E/model_size experts, runs those experts, combines its partial outputs, and
a single psum over the model axis sums the per-expert-shard partials:

  wire/device/layer = 2 * T_loc * d bytes (fwd psum + bwd psum)
                    ~ 0.25 GB vs 454 GB for granite-1b train_4k.

Routing work (top-k over the small (T_loc, E) logits) is replicated across
model shards -- negligible next to the expert matmuls. Falls back to the
GSPMD sort implementation when E % model_size != 0 (granite-3b's 40
experts) or when no mesh/model axis is available.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models.common import silu
from repro.models.moe import moe_block, router_topk
from repro.parallel.sharding import data_axes

__all__ = ["moe_block_sharded"]


def _local_dispatch_combine(x_loc, router, wg, wu, wd, cfg, model_axis,
                            data_axes_):
    """Runs per (data x model) shard. x_loc (T_loc, d); wg/wu/wd hold this
    shard's E_loc experts; router is the full (d, E) table (replicated)."""
    T_loc, d = x_loc.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    E_loc = wg.shape[0]
    m_id = jax.lax.axis_index(model_axis)
    e0 = m_id * E_loc

    gates, idx, aux = router_topk(x_loc, router, k)

    C = int(T_loc * k / E * cfg.capacity_factor)
    C = max(8, -(-C // 8) * 8)

    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)                       # local sort only
    sorted_e = flat_e[order]
    token_of = order // k
    first_of_e = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(T_loc * k) - first_of_e[sorted_e]
    local_e = sorted_e - e0
    mine = (local_e >= 0) & (local_e < E_loc) & (pos_in_e < C)
    slot = jnp.where(mine, local_e * C + pos_in_e, E_loc * C)

    buf = jnp.zeros((E_loc * C + 1, d), x_loc.dtype).at[slot].set(
        x_loc[token_of])
    xb = buf[:-1].reshape(E_loc, C, d)

    g = jnp.einsum("ecd,edf->ecf", xb, wg)
    u = jnp.einsum("ecd,edf->ecf", xb, wu)
    yb = jnp.einsum("ecf,efd->ecd", silu(g) * u, wd)

    ybf = jnp.concatenate([yb.reshape(E_loc * C, d),
                           jnp.zeros((1, d), yb.dtype)], 0)
    contrib = ybf[slot] * gates.reshape(-1)[order][:, None].astype(yb.dtype)
    y_partial = jnp.zeros((T_loc, d), x_loc.dtype).at[token_of].add(
        jnp.where(mine[:, None], contrib, 0.0))

    y = jax.lax.psum(y_partial, model_axis)           # the ONLY collective
    for ax in data_axes_:
        aux = jax.lax.pmean(aux, ax)
    return y, aux


def moe_block_sharded(x2d, params, cfg, mesh):
    """Drop-in for moe.moe_block with cfg.moe_impl == 'shard_map_local'."""
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.num_experts % mesh.shape["model"] != 0):
        return moe_block(x2d, params, cfg, mesh)

    daxes = data_axes(mesh)
    tok_spec = P(daxes if daxes else None)
    run = shard_map(
        partial(_local_dispatch_combine, cfg=cfg, model_axis="model",
                data_axes_=daxes),
        mesh=mesh,
        in_specs=(tok_spec,                     # tokens: data-sharded
                  P(),                          # router: replicated (small)
                  P("model"), P("model"), P("model")),  # experts: EP
        out_specs=(tok_spec, P()),
        check_vma=False)
    return run(x2d, params["router"], params["w_gate"], params["w_up"],
               params["w_down"])
