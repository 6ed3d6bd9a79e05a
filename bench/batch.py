"""The batch driver: ``plan.batched_hvp`` back to back for the window.

Traffic parameters (``bench/traffic/<name>.json``, ``"driver": "batch"``):

* ``input_sets`` -- distinct (A, V) pairs made on the device from the seed;
  the calls of the window cycle through them;
* ``a_low``, ``a_high`` -- A ~ U(a_low, a_high); V ~ N(0, 1).

The configuration gives n, m and the objective.  The plan is built with the
library's defaults, so the automatic choice of csize, backend and schedule
is what is measured.  Every output of the window is kept and compared, row
by row, with the float64 reference on the host CPU.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .common import Checked, device_key

__all__ = ["Driver"]


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _inputs(key, sets, m, n, a_low, a_high):
    ka, kv = jax.random.split(key)
    A = jax.random.uniform(ka, (sets, m, n), jnp.float32, a_low, a_high)
    V = jax.random.normal(kv, (sets, m, n), jnp.float32)
    return A, V


class Driver:
    def __init__(self, config, traffic: dict, seed: int):
        from repro import engine
        self.config = config
        n, m = config.spec["n"], config.spec["m"]
        self.n, self.m = n, m
        self.plan = engine.plan(config.module.objective(n), n, m=m)
        A, V = _inputs(device_key(seed), int(traffic["input_sets"]), m, n,
                       float(traffic["a_low"]), float(traffic["a_high"]))
        self.inputs = [(A[s], V[s]) for s in range(A.shape[0])]
        del A, V
        # every call of the window has this one shape: one call compiles it
        jax.block_until_ready(self.plan.batched_hvp(*self.inputs[0]))
        self.outputs = []
        self.elapsed_s = None

    def window(self, seconds: float) -> float:
        """Whole calls until ``seconds`` have passed; returns the window's
        length, which ends with the last call."""
        outs = []
        with jax.profiler.TraceAnnotation("bench:window"):
            t0 = time.perf_counter()
            i = 0
            while True:
                s = i % len(self.inputs)
                with jax.profiler.TraceAnnotation("bench:batched_hvp"):
                    out = self.plan.batched_hvp(*self.inputs[s])
                    out.block_until_ready()
                outs.append((s, out))
                i += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            self.elapsed_s = time.perf_counter() - t0
        self.outputs = outs
        return self.elapsed_s

    def end_to_end(self) -> dict:
        points = len(self.outputs) * self.m
        return {"points_per_s": points / self.elapsed_s}

    def counters(self) -> dict:
        return {"calls": len(self.outputs), "m": self.m, "n": self.n,
                "backend": self.plan.backend_for("batched_hvp"),
                "csize": self.plan.csize}

    def release(self) -> None:
        """Bring inputs and outputs to the host and free the device."""
        self.host_inputs = [(np.asarray(A), np.asarray(V))
                            for A, V in self.inputs]
        self.host_outputs = [(s, np.asarray(out)) for s, out in self.outputs]
        self.inputs, self.outputs = [], []

    def check(self) -> Checked:
        limit = float(self.config.spec["max_rel_err"])
        formula = self.config.module.formula(self.n)
        refs = [reference.hvp_float64(formula, A, V)
                for A, V in self.host_inputs]
        res = Checked(attempted=len(self.host_outputs))
        worst = 0.0
        for s, out in self.host_outputs:
            err = float(reference.row_rel_err(out, refs[s]).max())
            worst = max(worst, err)
            res.failed += not err <= limit
        res.add("max_row_rel_err", worst, limit)
        return res
