"""The chunk-leading hDual layout that the chess_hvp kernel builds equals
the default trailing layout up to a transpose, op by op; and the programs
of the trailing-layout backends are the ones they were before the
chunk-leading layout existed."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.hmath as hm
from repro import engine
from repro.compat import make_mesh
from repro.core import testfns
from repro.core.hdual import HDual

N, C = 6, 3


def _pair(seed: int, width: int):
    """The same random hDual (value shape (N, width)) in both layouts."""
    rng = np.random.RandomState(seed)
    val = rng.uniform(0.5, 2.0, (N, width))      # inside sqrt's domain
    di = rng.randn(N, width)
    dj = rng.randn(N, width, C)
    dij = rng.randn(N, width, C)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    trail = HDual(f32(val), f32(di), f32(dj), f32(dij))
    lead = HDual(f32(val), f32(di), f32(np.moveaxis(dj, -1, 0)),
                 f32(np.moveaxis(dij, -1, 0)), lead=True)
    return trail, lead


W = np.asarray(np.random.RandomState(9).randn(4, N), np.float32)
COEF = np.asarray(np.random.RandomState(10).randn(N, 1), np.float32)

OPS = {
    "getitem_slice": lambda x, y: x[1:],
    "getitem_int": lambda x, y: x[2],
    "getitem_tuple": lambda x, y: x[1:4, 2:],
    "add": lambda x, y: x + y,
    "add_const": lambda x, y: x + 1.5,
    "sub": lambda x, y: x - y,
    "rsub_const": lambda x, y: 1.0 - x,
    "mul": lambda x, y: x * y,
    "mul_const": lambda x, y: x * 2.5,
    "mul_coef": lambda x, y: x * COEF,
    "mul_offset_slices": lambda x, y: x[1:] * x[:-1],
    "pow_int": lambda x, y: x ** 3,
    "pow_zero": lambda x, y: x ** 0,
    "pow_frac": lambda x, y: x ** 2.5,
    "sum0": lambda x, y: x.sum(0),
    "sum_all": lambda x, y: x.sum(),
    "sin": lambda x, y: hm.sin(x),
    "cos": lambda x, y: hm.cos(x),
    "exp": lambda x, y: hm.exp(x),
    "sqrt": lambda x, y: hm.sqrt(x),
    "matvec_const": lambda x, y: hm.matvec_const(W, x),
    "where": lambda x, y: hm.where(x.val > y.val, x, y),
    "where_const": lambda x, y: hm.where(x.val > 1.0, x, 0.5),
    "abs": lambda x, y: hm.abs(x - 1.25),
    "reshape": lambda x, y: x[:, :4].reshape(2, 3, 4),
    # operands of different rank broadcast numpy-style in both layouts
    "mul_mixed_rank_row": lambda x, y: x * x[0],
    "mul_mixed_rank_sum": lambda x, y: x.sum(0) * y,
    "add_mixed_rank": lambda x, y: x + y[2],
    "sub_mixed_rank": lambda x, y: x.sum(0) - y,
    "sum_times_coef": lambda x, y: x.sum(0) * COEF,
    "add_const_higher_rank": lambda x, y: (x.sum(0) + COEF) * y,
    "where_mixed_rank": lambda x, y: hm.where(x.val > 1.0, x.sum(0), y),
    "where_row_cond": lambda x, y: hm.where(x.val[0] > 1.0, x, y[1]),
    "matvec_mixed_rank": lambda x, y: hm.matvec_const(W, x * y.sum(0)),
}


@pytest.mark.parametrize("width", [5, 128])
@pytest.mark.parametrize("op", sorted(OPS))
def test_chunk_leading_equals_trailing_up_to_transpose(op, width):
    """Width 128 fills one lane tile, as the kernel's default block does."""
    xt, xl = _pair(1, width)
    yt, yl = _pair(2, width)
    rt = OPS[op](xt, yt)
    rl = OPS[op](xl, yl)
    assert rl.lead and not rt.lead
    assert rl.csize == rt.csize == C
    for name in ("val", "di", "dj", "dij"):
        got = np.asarray(getattr(rl, name))
        want = np.asarray(getattr(rt, name))
        if name in ("dj", "dij"):
            want = np.moveaxis(want, -1, 0)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                   err_msg=f"{op}.{name}")


def test_layouts_do_not_mix():
    xt, xl = _pair(1, 5)
    with pytest.raises(ValueError, match="chunk layouts"):
        xt + xl
    with pytest.raises(ValueError, match="chunk layouts"):
        hm.where(xt.val > 1.0, xt, xl)


def test_chunk_leading_is_a_pytree_of_its_own_layout():
    _, xl = _pair(1, 5)
    leaves, tree = jax.tree_util.tree_flatten(xl)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert back.lead and back.csize == C


# ---------------------------------------------------------------------------
# the trailing layout's programs are unchanged
# ---------------------------------------------------------------------------

def _cases(family: str):
    n, m = 6, 3
    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    for fname in ("rosenbrock", "ackley", "fletcher_powell"):
        f = testfns.FUNCTIONS[fname](n)
        if family.startswith("pytree"):
            p = engine.plan(f, None, csize=4, backend=family)
            if family == "pytree_fwd":
                yield lambda a, v, p=p: p.quadform(a, v), (A[0], V[0])
            else:
                yield lambda a, v, p=p: p.hvp(a, v), (A[0], V[0])
            continue
        for sym in (False, True):
            if family == "sharded":
                p = engine.plan(f, n, csize=4, backend=family, symmetric=sym,
                                mesh=make_mesh((1,), ("data",)))
                yield lambda A, V, p=p: p.batched_hvp(A, V), (A, V)
            elif family == "sharded_rows":
                p = engine.plan(f, n, csize=4, backend=family, symmetric=sym,
                                mesh=make_mesh((1,), ("model",)))
                yield lambda a, v, p=p: p.hvp(a, v), (A[0], V[0])
            else:
                p = engine.plan(f, n, csize=4, backend=family, symmetric=sym)
                yield lambda A, V, p=p: p.batched_hvp(A, V), (A, V)
                yield lambda a, p=p: p.hessian(a), (A[0],)


# sha256 (first 16 hex digits) over the jaxprs of each family's cases,
# recorded on the tree before the chunk-leading layout was added, with
# jax 0.9.  The printed jaxpr changes with jax itself and with any
# deliberate change to testfns or to these backends' schedules; then, and
# only then, re-record on a tree whose trailing-layout code is the one the
# digests stand for (for a jax upgrade: the same commit, before and after):
#   PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_hdual_layout.py
TRAILING_PROGRAMS = {
    "pytree_fwd": "a2c1a49e0dbaa6dd",
    "pytree_fwdrev": "bd4230e114bc5b11",
    "reference": "075f8855082cbee2",
    "sharded": "a0b94559763323cd",
    "sharded_rows": "dbfafdc72b3054b9",
    "vmap_l0": "1a5395780b862de4",
    "vmap_l1": "a862d5d029d41bac",
    "vmap_l2": "e2d82c583696f089",
}


@pytest.mark.parametrize("family", sorted(TRAILING_PROGRAMS))
def test_trailing_layout_programs_unchanged(family):
    """The trailing-layout backends trace the very programs they traced
    before the kernel's chunk-leading layout existed, so their outputs are
    bit-for-bit the same."""
    assert _digest(family) == TRAILING_PROGRAMS[family]


def _digest(family: str) -> str:
    h = hashlib.sha256()
    for fn, args in _cases(family):
        h.update(str(jax.make_jaxpr(fn)(*args)).encode())
    return h.hexdigest()[:16]


if __name__ == "__main__":      # prints TRAILING_PROGRAMS for this tree
    for fam in sorted(TRAILING_PROGRAMS):
        print(f'    "{fam}": "{_digest(fam)}",')
