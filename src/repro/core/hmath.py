"""Math functions overloaded for HDual (the paper's sin/cos/exp/abs operators).

Every function accepts either an ``HDual`` or a plain array and dispatches
accordingly, so user functions written against ``hmath`` run unchanged on
values and on hDuals -- the JAX analogue of the paper's templated
``f<hDual<csize>>`` instantiation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .hdual import HDual, _val

__all__ = [
    "sin", "cos", "tan", "exp", "log", "sqrt", "tanh", "sigmoid", "abs",
    "where", "maximum", "minimum", "sum", "dot_const", "matvec_const",
    "square", "pow", "asin", "acos", "atan", "sinh", "cosh", "erf",
    "log1p", "expm1",
]


def _dispatch(u, g, dg, d2g):
    if isinstance(u, HDual):
        v = u.val
        return u.unary(g(v), dg(v), d2g(v))
    return g(u)


def sin(u):
    return _dispatch(u, jnp.sin, jnp.cos, lambda v: -jnp.sin(v))


def cos(u):
    return _dispatch(u, jnp.cos, lambda v: -jnp.sin(v), lambda v: -jnp.cos(v))


def tan(u):
    def d(v):
        s = 1.0 / jnp.cos(v)
        return s * s

    return _dispatch(u, jnp.tan, d, lambda v: 2.0 * jnp.tan(v) * d(v))


def exp(u):
    return _dispatch(u, jnp.exp, jnp.exp, jnp.exp)


def log(u):
    return _dispatch(u, jnp.log, lambda v: 1.0 / v, lambda v: -1.0 / (v * v))


def sqrt(u):
    def g(v):
        return jnp.sqrt(v)

    return _dispatch(u, g, lambda v: 0.5 / g(v), lambda v: -0.25 / (v * g(v)))


def tanh(u):
    def dg(v):
        t = jnp.tanh(v)
        return 1.0 - t * t

    return _dispatch(u, jnp.tanh, dg,
                     lambda v: -2.0 * jnp.tanh(v) * dg(v))


def sigmoid(u):
    def g(v):
        return 1.0 / (1.0 + jnp.exp(-v))

    def dg(v):
        s = g(v)
        return s * (1.0 - s)

    def d2g(v):
        s = g(v)
        return s * (1.0 - s) * (1.0 - 2.0 * s)

    return _dispatch(u, g, dg, d2g)


def abs(u):  # noqa: A001 - mirrors the paper's abs overload
    if isinstance(u, HDual):
        s = jnp.sign(u.val)
        # |u|' = sign(u) u' ; |u|'' = sign(u) u'' (a.e., matching the C++ lib)
        return u._new(jnp.abs(u.val), s * u.di, u.chunk(s) * u.dj,
                      u.chunk(s) * u.dij)
    return jnp.abs(u)


def asin(u):
    def dg(v):
        return 1.0 / jnp.sqrt(1.0 - v * v)

    return _dispatch(u, jnp.arcsin, dg,
                     lambda v: v * dg(v) ** 3)


def acos(u):
    def dg(v):
        return -1.0 / jnp.sqrt(1.0 - v * v)

    return _dispatch(u, jnp.arccos, dg,
                     lambda v: v * dg(v) / (1.0 - v * v))


def atan(u):
    def dg(v):
        return 1.0 / (1.0 + v * v)

    return _dispatch(u, jnp.arctan, dg,
                     lambda v: -2.0 * v * dg(v) ** 2)


def sinh(u):
    return _dispatch(u, jnp.sinh, jnp.cosh, jnp.sinh)


def cosh(u):
    return _dispatch(u, jnp.cosh, jnp.sinh, jnp.cosh)


def erf(u):
    import math as _m

    def dg(v):
        return (2.0 / _m.sqrt(_m.pi)) * jnp.exp(-v * v)

    return _dispatch(u, jax.scipy.special.erf, dg,
                     lambda v: -2.0 * v * dg(v))


def log1p(u):
    return _dispatch(u, jnp.log1p, lambda v: 1.0 / (1.0 + v),
                     lambda v: -1.0 / ((1.0 + v) * (1.0 + v)))


def expm1(u):
    return _dispatch(u, jnp.expm1, jnp.exp, jnp.exp)


def square(u):
    return u * u if isinstance(u, HDual) else jnp.square(u)


def pow(u, p):  # noqa: A001
    return u ** p


def where(c, a, b):
    """Branch select on the primal condition (paper's comparison overloads)."""
    if not (isinstance(a, HDual) or isinstance(b, HDual)):
        return jnp.where(c, a, b)
    h = a if isinstance(a, HDual) else b
    cs, lead = h.csize, h.lead
    shape = jnp.broadcast_shapes(jnp.shape(_val(a)), jnp.shape(_val(b)))
    if not isinstance(a, HDual):
        a = HDual.constant(jnp.broadcast_to(jnp.asarray(a), shape), cs,
                           lead=lead)
    if not isinstance(b, HDual):
        b = HDual.constant(jnp.broadcast_to(jnp.asarray(b), shape), cs,
                           lead=lead)
    if a.lead != b.lead:
        raise ValueError("HDual operands have different chunk layouts")
    if lead:    # operands of lower rank broadcast behind the chunk axis
        r = max(jnp.ndim(c), a.dj.ndim - 1, b.dj.ndim - 1)
        a, b = a._lift(r), b._lift(r)
        h = a
    cc = h.chunk(c) if jnp.ndim(c) else c
    return h._new(jnp.where(c, a.val, b.val), jnp.where(c, a.di, b.di),
                  jnp.where(cc, a.dj, b.dj), jnp.where(cc, a.dij, b.dij))


def maximum(a, b):
    c = _val(a) >= _val(b)
    return where(c, a, b)


def minimum(a, b):
    c = _val(a) <= _val(b)
    return where(c, a, b)


def sum(u, axis=None):  # noqa: A001
    return u.sum(axis) if isinstance(u, HDual) else jnp.sum(u, axis)


def matvec_const(A, u):
    """y = A @ u for a *constant* matrix A (p,n) and HDual vector u (n,) or
    (n, B).

    Linear maps act componentwise on all 2c+2 hDual slots -- this is the
    identity exploited by the fused hdual_linear kernel (DESIGN.md §3).
    Trailing layout: the chunk slots are contracted one chunk at a time
    (``x[..., l]``).  Chunk-leading layout (inside the ``chess_hvp``
    kernel, value shape (n, B) with the instances on the lanes): every
    component is a clean 2-D (n, B) slab, contracted slab by slab.
    ``Precision.HIGHEST`` keeps float32 products on the TPU, whose default
    matmul rounds inputs to bfloat16.
    """
    if not isinstance(u, HDual):
        return _mv(A, u)
    c = u.csize
    if not u.lead:
        def chunks(x):
            return jnp.stack([_mv(A, x[..., l]) for l in range(c)], -1)

        return HDual(_mv(A, u.val), _mv(A, u.di), chunks(u.dj),
                     chunks(u.dij))
    # an operand of lower rank (a broadcast constant term) is widened to S
    shape = jnp.shape(u.val)

    def slabs(x):
        return jnp.stack([_mv(A, jnp.broadcast_to(x[l], shape))
                          for l in range(c)])

    return HDual(_mv(A, u.val), _mv(A, jnp.broadcast_to(u.di, shape)),
                 slabs(u.dj), slabs(u.dij), lead=True)


def _mv(A, x):
    return jnp.matmul(A, x, precision=jax.lax.Precision.HIGHEST)


def dot_const(u, w):
    """<u, w> for HDual vector u (n,) and constant vector w (n,)."""
    if not isinstance(u, HDual):
        return u @ w
    return (u * w).sum(0)
