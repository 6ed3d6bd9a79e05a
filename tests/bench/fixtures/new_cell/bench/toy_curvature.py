"""A driver of pytree curvature: the HVP of a configuration's loss with
respect to its weights, one direction per call, back to back for the
window.

Traffic parameters (``"driver": "toy_curvature"``): ``probes`` --
directions made on the device from the seed, used in turn.  The
configuration gives ``num_layers``, ``width`` and ``batch``; weights, data
and directions come from the seed.  Every output of the window is compared
with the float64 reference (``formula``) on the host CPU.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .common import Checked, device_key

__all__ = ["Driver", "control_reading", "SMALL"]

SMALL = {"config": {"batch": 16}, "traffic": {"probes": 2}}


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _inputs(key, layers, width, batch, probes):
    kp, kx, kv = jax.random.split(key, 3)

    def tree(k):
        ks = jax.random.split(k, 2 * layers)
        return [{"w": jax.random.normal(ks[2 * i], (width, width))
                 / jnp.sqrt(width),
                 "b": 0.1 * jax.random.normal(ks[2 * i + 1], (width,))}
                for i in range(layers)]

    x = jax.random.normal(kx, (batch, width))
    return tree(kp), x, [tree(k) for k in jax.random.split(kv, probes)]


def _make(config, traffic, seed):
    s = config.spec
    return _inputs(device_key(seed), int(s["num_layers"]), int(s["width"]),
                   int(s["batch"]), int(traffic["probes"]))


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(x, np.float64))
                           for x in jax.tree.leaves(tree)])


def _hvp_in(formula, params, x, v, dtype: str, device=None):
    """The reference HVP in ``dtype`` on ``device``, flattened."""
    dt = jnp.dtype(dtype)
    cast = partial(jax.tree.map, lambda a: jax.device_put(
        np.asarray(a, np.float64 if dt == jnp.float64 else np.float32),
        device).astype(dt))
    p, xx, vv = cast(params), cast(x), cast(v)
    return _flat(jax.jvp(jax.grad(lambda q: formula(q, xx)), (p,), (vv,))[1])


def _float64(formula, params, x, v) -> np.ndarray:
    with jax.enable_x64(True):
        return _hvp_in(formula, params, x, v, "float64",
                       jax.devices("cpu")[0])


def _work(config, params, x):
    """Operations of the reference HVP (XLA's count, lowered for the host
    CPU) and the least bytes: weights, direction and result, and the
    data, once each."""
    formula = config.module.formula(config.spec)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          (params, x))

    def hvp(p, xx, v):
        return jax.jvp(jax.grad(lambda q: formula(q, xx)), (p,), (v,))[1]

    with jax.default_device(jax.devices("cpu")[0]):
        cost = jax.jit(hvp).lower(*shapes, shapes[0]).cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    weights = sum(a.size * 4 for a in jax.tree.leaves(params))
    return float(cost["flops"]), 3.0 * weights + x.size * 4


class Driver:
    def __init__(self, config, traffic: dict, seed: int):
        self.config = config
        self.params, self.x, self.vs = _make(config, traffic, seed)
        self.hvp = jax.jit(config.module.objective(config.spec))
        jax.block_until_ready(self.hvp(self.params, self.x, self.vs[0]))
        self.flops, self.bytes = _work(config, self.params, self.x)
        self.outputs = []

    def window(self, seconds: float) -> float:
        outs = []
        with jax.profiler.TraceAnnotation("bench:window"):
            t0 = time.perf_counter()
            i = 0
            while True:
                k = i % len(self.vs)
                with jax.profiler.TraceAnnotation("bench:toy_hvp"):
                    out = self.hvp(self.params, self.x, self.vs[k])
                    jax.block_until_ready(out)
                outs.append((k, out))
                i += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            self.elapsed_s = time.perf_counter() - t0
        self.outputs = outs
        return self.elapsed_s

    def end_to_end(self) -> dict:
        return {"points_per_s": len(self.outputs) / self.elapsed_s}

    def counters(self) -> dict:
        return {"calls": len(self.outputs), "flops_per_call": self.flops,
                "bytes_per_call": self.bytes}

    def release(self) -> None:
        to_host = partial(jax.tree.map, np.asarray)
        self.host = to_host((self.params, self.x, self.vs))
        self.host_outputs = [(k, _flat(out)) for k, out in self.outputs]
        self.params = self.x = self.vs = None
        self.outputs = []

    def check(self) -> Checked:
        limit = float(self.config.spec["max_rel_err"])
        formula = self.config.module.formula(self.config.spec)
        params, x, vs = self.host
        refs = [_float64(formula, params, x, v) for v in vs]
        res = Checked(attempted=len(self.host_outputs))
        worst = 0.0
        for k, out in self.host_outputs:
            err = float(reference.row_rel_err(out[None], refs[k][None])[0])
            worst = max(worst, err)
            res.failed += not err <= limit
        res.add("max_probe_rel_err", worst, limit)
        return res


def control_reading(config, traffic: dict, seed: int, dtype: str) -> float:
    """The control on the cell's directions: the reference computed in
    ``dtype`` in the program's place; its widest per-probe gap."""
    formula = config.module.formula(config.spec)
    params, x, vs = jax.tree.map(np.asarray, _make(config, traffic, seed))
    worst = 0.0
    for v in vs:
        low = _hvp_in(formula, params, x, v, dtype)
        ref = _float64(formula, params, x, v)
        worst = max(worst, float(reference.row_rel_err(low[None],
                                                        ref[None])[0]))
    return worst
