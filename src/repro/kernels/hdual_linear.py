"""hdual_linear: fused (2c+2)-component hDual affine map  Y[k] = X[k] @ W.

Linear maps act componentwise on hDual slots (d(xW) = (dx)W, d2(xW) =
(d2x)W), so pushing an hDual through a linear layer is 2c+2 independent
matmuls AGAINST THE SAME WEIGHT MATRIX. A naive sequential implementation
re-reads each W tile 2c+2 times from HBM; this kernel loads each (bk, bo)
W tile into VMEM ONCE per grid cell and contracts ALL components against it
with one batched dot_general -- arithmetic intensity rises ~(2c+2)x, the TPU
re-statement of the paper's "share the function evaluation across
derivatives" (DESIGN.md §3).

Grid: (T/bt, dout/bo, din/bk), accumulating over the k (din) grid axis into
a VMEM-resident output block; MXU-aligned tile defaults (128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.chess_hvp import resolve_interpret

__all__ = ["hdual_linear_pallas"]


def _kernel(x_ref, w_ref, o_ref, *, acc_dtype):
    k = pl.program_id(2)
    x = x_ref[...]                                  # (K2, bt, bk)
    w = w_ref[...]                                  # (bk, bo)
    y = jax.lax.dot_general(
        x, w, (((2,), (0,)), ((), ())),
        preferred_element_type=acc_dtype)           # (K2, bt, bo)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = y.astype(o_ref.dtype)

    @pl.when(k > 0)
    def _acc():
        o_ref[...] = o_ref[...] + y.astype(o_ref.dtype)


def hdual_linear_pallas(x, w, *, bt: int = 128, bo: int = 128, bk: int = 128,
                        interpret: bool | None = None):
    """x: (K2, T, din) stacked hDual components; w: (din, dout).
    Returns (K2, T, dout). Tiles clamp to the actual dims.  ``interpret``
    follows ``chess_hvp.resolve_interpret``."""
    K2, T, din = x.shape
    dout = w.shape[1]
    assert w.shape[0] == din
    bt, bo, bk = min(bt, T), min(bo, dout), min(bk, din)
    assert T % bt == 0 and dout % bo == 0 and din % bk == 0, \
        (T, din, dout, bt, bk, bo)
    grid = (T // bt, dout // bo, din // bk)

    return pl.pallas_call(
        functools.partial(_kernel, acc_dtype=jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K2, bt, bk), lambda t, o, k: (0, t, k)),
            pl.BlockSpec((bk, bo), lambda t, o, k: (k, o)),
        ],
        out_specs=pl.BlockSpec((K2, bt, bo), lambda t, o, k: (0, t, o)),
        out_shape=jax.ShapeDtypeStruct((K2, T, dout), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, w)
