"""Fletcher-Powell, as arXiv:2410.22575 section 7 evaluates it.

    f(x) = sum_i (sum_j A_ij sin x_j + B_ij cos x_j - E_i)^2,
    E = A sin(alpha) + B cos(alpha)

with integer A, B in [-100, 100] and alpha in [-pi, pi] (Fletcher and
Powell, 1963).  ``coefficients`` is the benchmark's own copy of the
library's seeded generator (one coefficient set per n, seed 1963 + n), so
the reference is the same function and takes no table from the program.
"""

import jax.numpy as jnp
import numpy as np

SEED = 1963


def objective(n):
    from repro.core import testfns
    return testfns.make_fletcher_powell(n)


def serve_plans():
    from repro.launch.serve import build_plans
    return build_plans(("fletcher_powell",))


def coefficients(n):
    rng = np.random.RandomState(SEED + n)
    a = rng.randint(-100, 101, size=(n, n)).astype(np.float32)
    b = rng.randint(-100, 101, size=(n, n)).astype(np.float32)
    alpha = rng.uniform(-np.pi, np.pi, size=(n,)).astype(np.float32)
    e = (a @ np.sin(alpha) + b @ np.cos(alpha)).astype(np.float32)
    return a, b, e


def formula(n):
    a, b, e = coefficients(n)

    def f(x):
        dt = x.dtype
        r = (jnp.dot(a.astype(dt), jnp.sin(x), precision="highest")
             + jnp.dot(b.astype(dt), jnp.cos(x), precision="highest")
             - e.astype(dt))
        return jnp.sum(r * r)
    return f
