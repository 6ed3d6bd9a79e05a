"""The work of a batched HVP, counted the same whatever implements it.

Operations are those of the benchmark's own reference -- ``jax.jvp`` of
``jax.grad`` of the configuration's plain formula, vmapped over (m, n) in
float32 -- as XLA's cost analysis counts them on the lowered, unoptimised
program.  Bytes are the least any implementation must move: A and V read
and the result written once, 3 m n 4 bytes.  The roofline time is the
larger of operations over peak FLOP/s and bytes over peak bytes/s.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = ["Work", "hvp_work", "roofline_s"]


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def hvp_work(formula, m: int, n: int) -> Work:
    def one(a, v):
        return jax.jvp(jax.grad(formula), (a,), (v,))[1]

    x = jax.ShapeDtypeStruct((m, n), jnp.float32)
    # lowered for the host CPU: a backend behind the PJRT C API (the TPU)
    # has no cost analysis of a lowered program, and the count of the
    # unoptimised program does not depend on the backend
    with jax.default_device(jax.devices("cpu")[0]):
        cost = jax.jit(jax.vmap(one)).lower(x, x).cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return Work(flops=float(cost["flops"]), bytes=3.0 * m * n * 4)


def roofline_s(work: Work, peaks) -> tuple:
    """(least time on the chip, "compute" or "memory": which bounds it)."""
    t_flops = work.flops / peaks.flops
    t_bytes = work.bytes / peaks.hbm_bytes
    return (t_flops, "compute") if t_flops > t_bytes else (t_bytes,
                                                            "memory")
