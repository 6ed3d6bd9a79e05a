"""Rosenbrock, as arXiv:2410.22575 section 7 evaluates it.

``objective`` and ``serve_plans`` are the library's side: the function a
user brings to ``engine.plan`` and the plans ``launch/serve.py`` deploys.
``formula`` is the benchmark's own plain copy of the function, from which
the reference HVP is differentiated; it takes nothing from the program.
"""

import jax.numpy as jnp


def objective(n):
    from repro.core import testfns
    return testfns.rosenbrock


def serve_plans():
    from repro.launch.serve import build_plans
    return build_plans(("rosenbrock",))


def formula(n):
    """sum_{k<n-1} 100 (x_{k+1} - x_k^2)^2 + (1 - x_k)^2."""
    def f(x):
        return jnp.sum(100.0 * (x[1:] - x[:-1] ** 2) ** 2
                       + (1.0 - x[:-1]) ** 2)
    return f
