"""hDual: the CHESSFAD second-order forward-mode dual number (paper §3-4).

An ``HDual`` carries, for every program value ``u``:

  val : u                                  -- the primal value
  di  : du/dx_i                            -- tangent w.r.t. the Hessian *row*
  dj  : du/dx_{j..j+c-1}    (chunk axis)   -- first-order chunk tangents
  dij : d2u/dx_i dx_{j..j+c-1}             -- second-order chunk

TPU adaptation (DESIGN.md §3): the paper stores ``v[2*csize+2]`` scalars per
CUDA thread; here ``val``/``di``/``dj``/``dij`` are jnp arrays and every
overloaded op is one array op per component.  HDual is a registered pytree,
so ``jit``/``vmap``/``grad``/``shard_map`` compose with it -- the JAX
analogue of the paper's "header-based library: retype double -> hDual".

Shapes: ``val`` and ``di`` share a shape ``S``.  The chunk components have
one of two layouts, fixed per value:

* trailing (the default, every vmap/reference/sharded/pytree path):
  ``dj`` and ``dij`` are ``S + (csize,)``;
* chunk-leading (``lead=True``, built only inside the ``chess_hvp``
  kernel): ``dj`` and ``dij`` are ``(csize,) + S``, so with ``S = (n, B)``
  each chunk is a clean ``(n, B)`` slab whose instance axis ``B`` rides
  the 128 VPU lanes, like ``val`` and ``di``.

Binary ops broadcast ``S`` numpy-style; both operands must share a layout.
Numpy aligns shapes from the right, which lines ``S`` up in the trailing
layout; in the chunk-leading layout an operand of lower rank (``x[0]``,
``x.sum(0)``, a constant) first gets unit axes in front of ``S``
(``_lift``), so its chunk axis stays in front.
"""

from __future__ import annotations

import operator
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["HDual", "lift", "seed_point", "is_hdual"]


@jax.tree_util.register_pytree_node_class
class HDual:
    """CHESSFAD hDual<csize> (paper §4) with array components."""

    __slots__ = ("val", "di", "dj", "dij", "lead")
    # Make jnp.asarray & friends defer to our reflected operators.
    __array_priority__ = 1000

    def __init__(self, val, di, dj, dij, lead=False):
        self.val = val
        self.di = di
        self.dj = dj
        self.dij = dij
        self.lead = lead            # chunk axis leading (kernel) or trailing

    def _new(self, val, di, dj, dij):
        return HDual(val, di, dj, dij, self.lead)

    def chunk(self, x):
        """Broadcast a value-shaped array against this value's chunk axis.

        Chunk-leading: ``x`` may have a lower rank than ``S``; it gets unit
        axes in front up to the rank of ``(csize,) + S``."""
        if not self.lead:
            return x[..., None]
        return jnp.expand_dims(x, tuple(range(self.dj.ndim - jnp.ndim(x))))

    def _lift(self, rank):
        """Chunk-leading: unit axes in front of ``S`` up to ``rank``, so a
        lower-rank operand broadcasts numpy-style behind its chunk axis.
        The trailing layout needs nothing: numpy aligns ``S`` itself."""
        k = self.dj.ndim - 1
        if not self.lead or rank <= k:
            return self
        ax = tuple(range(1, 1 + rank - k))
        return self._new(self.val, self.di, jnp.expand_dims(self.dj, ax),
                         jnp.expand_dims(self.dij, ax))

    def _align(self, o):
        """This value and HDual ``o`` lifted to one rank (``_lift``)."""
        r = max(self.dj.ndim, o.dj.ndim) - 1
        return self._lift(r), o._lift(r)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        # aux None for the default layout: trailing HDual treedefs unchanged
        return (self.val, self.di, self.dj, self.dij), self.lead or None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, lead=bool(aux))

    # -- metadata ----------------------------------------------------------
    @property
    def csize(self) -> int:
        return self.dj.shape[0] if self.lead else self.dj.shape[-1]

    @property
    def shape(self):
        return jnp.shape(self.val)

    @property
    def dtype(self):
        return jnp.result_type(self.val)

    def __repr__(self):
        return (f"HDual(val={self.val!r}, di={self.di!r}, dj={self.dj!r}, "
                f"dij={self.dij!r})")

    # -- constructors --------------------------------------------------------
    @classmethod
    def constant(cls, x, csize, dtype=None, lead=False):
        x = jnp.asarray(x, dtype=dtype)
        z = jnp.zeros_like(x)
        cshape = (csize,) + x.shape if lead else x.shape + (csize,)
        zc = jnp.zeros(cshape, x.dtype)
        return cls(x, z, zc, zc, lead)

    # -- arithmetic ----------------------------------------------------------
    def _coerce(self, other):
        """Return ``other`` as HDual or None if it is a plain constant."""
        if isinstance(other, HDual):
            if other.lead != self.lead:
                raise ValueError("HDual operands have different chunk layouts")
            return other
        if isinstance(other, (int, float, np.ndarray, jnp.ndarray, np.number)):
            return None  # constant fast path
        return NotImplemented

    def __add__(self, other):
        o = self._coerce(other)
        if o is NotImplemented:
            return NotImplemented
        if o is None:  # constant: only the value moves (paper's op+(double, hDual))
            u = self._lift(jnp.ndim(other))
            return u._new(u.val + other, u.di, u.dj, u.dij)
        u, o = self._align(o)
        return u._new(u.val + o.val, u.di + o.di, u.dj + o.dj,
                      u.dij + o.dij)

    __radd__ = __add__

    def __neg__(self):
        return self._new(-self.val, -self.di, -self.dj, -self.dij)

    def __sub__(self, other):
        o = self._coerce(other)
        if o is NotImplemented:
            return NotImplemented
        if o is None:
            u = self._lift(jnp.ndim(other))
            return u._new(u.val - other, u.di, u.dj, u.dij)
        u, o = self._align(o)
        return u._new(u.val - o.val, u.di - o.di, u.dj - o.dj,
                      u.dij - o.dij)

    def __rsub__(self, other):
        return (-self).__add__(other)

    def __mul__(self, other):
        o = self._coerce(other)
        if o is NotImplemented:
            return NotImplemented
        if o is None:  # constant scale: all 2c+2 components scale (paper op*(hDual,double))
            c = jnp.asarray(other)
            u = self._lift(c.ndim)
            return u._new(u.val * c, u.di * c, u.dj * u.chunk(c),
                          u.dij * u.chunk(c))
        u, v = self._align(o)
        ch = u.chunk
        # Leibniz to second order (paper §3.1):
        #   (uv)_ij = u v_ij + u_i v_j + v_i u_j + v u_ij
        val = u.val * v.val
        di = u.val * v.di + v.val * u.di
        dj = ch(u.val) * v.dj + ch(v.val) * u.dj
        dij = (ch(u.val) * v.dij + ch(u.di) * v.dj
               + ch(v.di) * u.dj + ch(v.val) * u.dij)
        return u._new(val, di, dj, dij)

    __rmul__ = __mul__

    def _reciprocal(self):
        # g(v)=1/v, g'=-1/v^2, g''=2/v^3
        inv = 1.0 / self.val
        return self.unary(inv, -inv * inv, 2.0 * inv * inv * inv)

    def __truediv__(self, other):
        o = self._coerce(other)
        if o is NotImplemented:
            return NotImplemented
        if o is None:
            return self * (1.0 / jnp.asarray(other))
        return self * o._reciprocal()

    def __rtruediv__(self, other):
        return self._reciprocal() * other

    def __pow__(self, p):
        if isinstance(p, HDual):
            # u**p = exp(p*log(u)) -- delegate to hmath at call sites; rare.
            raise NotImplementedError("HDual**HDual: use hmath.exp(p*hmath.log(u))")
        if isinstance(p, int) and p >= 0:
            # Exact integer powers via repeated squaring keeps tests bitwise-stable
            # for the paper's polynomial test functions.
            if p == 0:
                return HDual.constant(jnp.ones_like(self.val), self.csize,
                                      lead=self.lead)
            result = None
            base = self
            e = p
            while e:
                if e & 1:
                    result = base if result is None else result * base
                e >>= 1
                if e:
                    base = base * base
            return result
        v = self.val
        g = v ** p
        dg = p * v ** (p - 1)
        d2g = p * (p - 1) * v ** (p - 2)
        return self.unary(g, dg, d2g)

    def unary(self, g, dg, d2g):
        """Chain rule for g(u): (paper §3.1 sin-rule generalized)

          g_i  = g'(u) u_i
          g_ij = g'(u) u_ij + g''(u) u_i u_j
        """
        ch = self.chunk
        return self._new(
            g,
            dg * self.di,
            ch(dg) * self.dj,
            ch(dg) * self.dij + ch(d2g * self.di) * self.dj,
        )

    # -- comparisons (on the primal value, like the paper's <,>,<= overloads) --
    def __lt__(self, other):
        return self.val < _val(other)

    def __le__(self, other):
        return self.val <= _val(other)

    def __gt__(self, other):
        return self.val > _val(other)

    def __ge__(self, other):
        return self.val >= _val(other)

    # -- structural ops ------------------------------------------------------
    def __getitem__(self, idx):
        # Index applies to the value shape S; the chunk axis is untouched.
        # Only basic (int/slice/tuple-of-those) indexing.
        cidx = idx
        if self.lead:
            cidx = (slice(None),) + (idx if isinstance(idx, tuple) else (idx,))
        return self._new(self.val[idx], self.di[idx], self.dj[cidx],
                         self.dij[cidx])

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        c = (self.csize,)
        cshape = c + shape if self.lead else shape + c
        return self._new(self.val.reshape(shape), self.di.reshape(shape),
                         self.dj.reshape(cshape), self.dij.reshape(cshape))

    def sum(self, axis=None):
        ax = _norm_axis(axis, jnp.ndim(self.val))
        cax = tuple(a + 1 for a in ax) if self.lead else ax
        return self._new(jnp.sum(self.val, ax), jnp.sum(self.di, ax),
                         jnp.sum(self.dj, cax), jnp.sum(self.dij, cax))

    def astype(self, dtype):
        return self._new(self.val.astype(dtype), self.di.astype(dtype),
                         self.dj.astype(dtype), self.dij.astype(dtype))


def _val(x):
    return x.val if isinstance(x, HDual) else x


def _norm_axis(axis, ndim):
    """Normalize value-shape axes so they never touch the chunk axis."""
    if axis is None:
        return tuple(range(ndim))
    if isinstance(axis, int):
        axis = (axis,)
    return tuple(a % ndim for a in axis)


def is_hdual(x) -> bool:
    return isinstance(x, HDual)


def lift(x, csize, dtype=None) -> HDual:
    """Lift a constant array into an HDual with zero derivatives."""
    return HDual.constant(x, csize, dtype)


def seed_point(a, i, cstart, csize) -> HDual:
    """CHUNK-INIT (paper Alg. 4): seed the n input variables.

    a      : (..., n) evaluation point
    i      : Hessian row index (scalar, may be traced)
    cstart : chunk start column (scalar, may be traced)

    Returns the HDual vector y with
      y.di[k]    = [k == i]
      y.dj[k, l] = [k == cstart + l]
    """
    a = jnp.asarray(a)
    n = a.shape[-1]
    dt = a.dtype
    k = jnp.arange(n)
    di = (k == i).astype(dt)
    di = jnp.broadcast_to(di, a.shape)
    cols = cstart + jnp.arange(csize)
    dj = (k[:, None] == cols[None, :]).astype(dt)
    dj = jnp.broadcast_to(dj, a.shape + (csize,))
    dij = jnp.zeros(a.shape + (csize,), dt)
    return HDual(a, di, dj, dij)
