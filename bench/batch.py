"""The batch driver: ``plan.batched_hvp`` back to back for the window.

Traffic parameters (``bench/traffic/<name>.json``, ``"driver": "batch"``):

* ``input_sets`` -- distinct (A, V) pairs made on the device from the seed;
  the calls of the window cycle through them;
* ``a_low``, ``a_high`` -- A ~ U(a_low, a_high); V ~ N(0, 1);
* ``lead_s`` -- seconds of calls dispatched ahead of the one waited for,
  so that the chip keeps working while the host stalls;
* ``lead_bytes`` -- the most bytes of output those calls may hold, so
  that a faster kernel does not fill the device with them;
* ``checks`` -- about this many outputs of the window, each call's drawn
  from the seed with the same chance, are kept and compared.

The configuration gives n, m and the objective.  The plan is built with the
library's defaults, so the automatic choice of csize, backend and schedule
is what is measured.  The kept outputs of the window are compared, row by
row, with the float64 reference on the host CPU.  ``control_reading`` gives
the control of that comparison on the same input sets.
"""

from __future__ import annotations

import math
import time
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .common import Checked, device_key, host_rng

__all__ = ["Driver", "control_reading", "SMALL"]

# the overrides that run a cell of this driver at a size a CPU test can hold
SMALL = {"config": {"m": 256}, "traffic": {"lead_s": 0.05, "checks": 10**6}}


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
        # the lead in calls: lead_s over the least of three calls' times,
        # and no more than lead_bytes of outputs
        times = []
        for s in range(3):
            t = time.perf_counter()
            jax.block_until_ready(
                self.plan.batched_hvp(*self.inputs[s % len(self.inputs)]))
            times.append(time.perf_counter() - t)
        self.call_s = min(times)
        out_bytes = m * n * np.dtype(np.float32).itemsize
        self.depth = max(1, min(
            math.ceil(float(traffic["lead_s"]) / self.call_s),
            int(float(traffic["lead_bytes"]) // out_bytes)))
        self.checks = int(traffic["checks"])
        self.check_rng = host_rng(seed, 2)
        self.outputs = []
        self.calls = 0
        self.elapsed_s = None

    def window(self, seconds: float) -> float:
        """Calls dispatched ``depth`` ahead of the one waited for, until
        ``seconds`` have passed; then nothing more is sent, all that was
        sent is waited for, and the window ends.  Returns its length."""
        keep = min(1.0, self.checks / (seconds / self.call_s + self.depth))
        kept, pending = [], deque()
        with jax.profiler.TraceAnnotation("bench:window"):
            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < seconds:
                s = i % len(self.inputs)
                with jax.profiler.TraceAnnotation("bench:batched_hvp"):
                    out = self.plan.batched_hvp(*self.inputs[s])
                pending.append(out)
                if self.check_rng.random() < keep:
                    kept.append((s, out))
                i += 1
                if len(pending) > self.depth:
                    pending.popleft().block_until_ready()
            jax.block_until_ready(list(pending))
            self.elapsed_s = time.perf_counter() - t0
        self.calls = i
        self.outputs = kept
        return self.elapsed_s

    def end_to_end(self) -> dict:
        points = self.calls * self.m
        return {"points_per_s": points / self.elapsed_s}

    def counters(self) -> dict:
        return {"calls": self.calls, "checked_calls": len(self.outputs),
                "depth": self.depth, "m": self.m, "n": self.n,
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


def control_reading(config, traffic: dict, seed: int, dtype: str) -> float:
    """The control on the cell's input sets: the reference computed in
    ``dtype`` in the program's place; its widest per-row gap."""
    n, m = config.spec["n"], config.spec["m"]
    A, V = _inputs(device_key(seed), int(traffic["input_sets"]), m, n,
                   float(traffic["a_low"]), float(traffic["a_high"]))
    worst = 0.0
    for s in range(A.shape[0]):
        worst = max(worst, reference.widest_gap(
            config.module.formula(n), np.asarray(A[s]), np.asarray(V[s]),
            dtype))
    return worst
