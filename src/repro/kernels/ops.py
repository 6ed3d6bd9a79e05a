"""jit'd public wrappers for the Pallas kernels + the engine's ``pallas``
backend registration.

``interpret`` defaults to True off-TPU (the kernels are TPU-target; CPU runs
them through the Pallas interpreter for correctness).  On TPU Mosaic
compiles them, and asking for the interpreter there is an error
(``chess_hvp.resolve_interpret``).

Kernel-compatible forms of a target function are discovered via the
``pallas_fn`` / ``pallas_consts`` attributes (see testfns.make_fletcher_
powell) instead of hard-coded name dispatch: any hmath-written f whose
value shape broadcasts over trailing instance axes runs as-is; functions
needing constant coefficient refs attach an adapter.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import testfns
from repro.engine.registry import BackendSpec, register_backend
from repro.kernels.chess_hvp import LANES, chess_hvp_pallas, resolve_interpret
from repro.kernels.hdual_linear import hdual_linear_pallas

__all__ = ["chess_hvp", "hdual_linear", "hdual_linear_apply", "kernel_form"]


def kernel_form(f):
    """(kernel_fn, consts) for any engine target function."""
    return (getattr(f, "pallas_fn", f),
            tuple(getattr(f, "pallas_consts", ())))


def _fn_and_consts(function: str, n: int):
    """Back-compat named lookup, now routed through the adapter protocol."""
    return kernel_form(testfns.FUNCTIONS[function](n))


# ---------------------------------------------------------------------------
# engine backend: the paper's Fig. 2 L2 kernel
# ---------------------------------------------------------------------------

def _pallas_supports(plan, workload):
    # v2 kernel serves any (m, n, csize): ragged tails are masked in-kernel
    # and the instance axis is padded to a blk_m multiple.  The only
    # remaining veto: a mesh-carrying plan asked for sharding -- never
    # steal it from the sharded backend even where pallas outranks it (TPU)
    return plan.mesh is None and plan.n is not None


def _pallas_make(plan, workload):
    kernel_f, consts = kernel_form(plan.f)
    # resolved once per executable: a TPU plan asking for the interpreter
    # fails before anything is traced
    interpret = resolve_interpret(plan.opt("interpret"))
    # the wrapper pads m up to a block multiple, so blk_m is purely a
    # tuning dial (the joint autotuner sweeps it); default to one lane
    # tile, which block_rows caps at m so small batches are one block
    blk_m = plan.opt("blk_m") or LANES

    def run(A, V):
        return chess_hvp_pallas(kernel_f, A, V, plan.csize, consts=consts,
                                blk_m=blk_m, symmetric=plan.symmetric,
                                interpret=interpret)
    return run


register_backend(BackendSpec(
    name="pallas", make=_pallas_make,
    workloads=frozenset({"batched_hvp"}),
    # Mosaic-compiled on TPU this is the fastest batched path; in CPU
    # interpret mode it is a correctness path only, so auto never picks it
    priority=40 if jax.default_backend() == "tpu" else -5,
    supports=_pallas_supports,
    doc="Fig. 2 L2 grid kernel v2 (symmetric + ragged; Pallas; Mosaic on "
        "TPU, interpreter elsewhere)"))


@partial(jax.jit, static_argnames=("function", "csize", "blk_m", "symmetric",
                                   "interpret"))
def chess_hvp(A, V, *, function: str = "rosenbrock", csize: int = 4,
              blk_m: int = LANES, symmetric: bool = False,
              interpret: bool | None = None):
    """Batched HVP on one of the paper's test-function families.

    A, V: (m, n) -> (m, n)."""
    n = A.shape[-1]
    f, consts = _fn_and_consts(function, n)
    return chess_hvp_pallas(f, A, V, csize, consts=consts, blk_m=blk_m,
                            symmetric=symmetric, interpret=interpret)


@partial(jax.jit, static_argnames=("bt", "bo", "bk", "interpret"))
def hdual_linear(x, w, *, bt: int = 128, bo: int = 128, bk: int = 128,
                 interpret: bool | None = None):
    """Fused hDual component matmul: x (K2, T, din) @ w (din, dout)."""
    return hdual_linear_pallas(x, w, bt=bt, bo=bo, bk=bk,
                               interpret=interpret)


def hdual_linear_apply(hd, w, **kw):
    """Apply the fused kernel to an HDual whose value shape is (din,) or
    (T, din): stacks [val, di, dj..., dij...] on a leading component axis,
    runs ONE kernel call (every component contracts the same W tiles),
    unstacks. Equivalent to hmath.matvec_const(w.T, hd) for vectors."""
    from repro.core.hdual import HDual

    c = hd.csize
    vec = hd.val.ndim == 1
    comps = jnp.concatenate([
        hd.val[None], hd.di[None],
        jnp.moveaxis(hd.dj, -1, 0), jnp.moveaxis(hd.dij, -1, 0)], axis=0)
    if vec:
        comps = comps[:, None, :]                    # (2c+2, 1, din)
    y = hdual_linear(comps, w, **kw)                 # (2c+2, T, dout)
    if vec:
        y = y[:, 0, :]
    return HDual(y[0], y[1],
                 jnp.moveaxis(y[2:2 + c], 0, -1),
                 jnp.moveaxis(y[2 + c:], 0, -1))
