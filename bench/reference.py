"""The reference that decides ``correct``, and its comparison.

The reference HVP is ``jax.jvp`` of ``jax.grad`` of a configuration's
plain ``formula``, vmapped over rows.  ``hvp_float64`` runs it in float64
on the host CPU; ``hvp_in`` runs it in another dtype on a given device,
which is how the control (the reference one precision lower, put in the
program's place) is computed.  Both work in blocks of rows so that they
fit beside anything else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["hvp_float64", "hvp_in", "row_rel_err", "widest_gap",
           "BLOCK_ROWS"]

BLOCK_ROWS = 65536


def _batched(f, dtype_name: str):
    dt = jnp.dtype(dtype_name)
    out = jnp.float64 if dt == jnp.float64 else jnp.float32

    def one(a, v):
        return jax.jvp(jax.grad(f), (a,), (v,))[1]

    def run(A, V):
        return jax.vmap(one)(A.astype(dt), V.astype(dt)).astype(out)
    return jax.jit(run)


def _blocks(fn, A, V, device):
    out = []
    for s in range(0, A.shape[0], BLOCK_ROWS):
        a = jax.device_put(A[s:s + BLOCK_ROWS], device)
        v = jax.device_put(V[s:s + BLOCK_ROWS], device)
        out.append(np.asarray(fn(a, v)))
    return np.concatenate(out) if out else np.zeros(A.shape, np.float64)


def hvp_float64(formula, A, V) -> np.ndarray:
    """Reference HVPs of rows (A, V) in float64 on the host CPU."""
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        A = np.asarray(A, np.float64)
        V = np.asarray(V, np.float64)
        return _blocks(_batched(formula, "float64"), A, V, cpu)


def hvp_in(formula, A, V, dtype: str, device=None) -> np.ndarray:
    """The same HVPs computed in ``dtype`` (the control), returned as
    float32, on ``device`` (the default device if None)."""
    device = device or jax.devices()[0]
    return _blocks(_batched(formula, dtype), np.asarray(A, np.float32),
                   np.asarray(V, np.float32), device)


def row_rel_err(got, want) -> np.ndarray:
    """Per row: max |got - want| / max |want|.  A row with a value that is
    not finite, or of the wrong shape, reads inf."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return np.full(want.shape[:1], np.inf)
    gap = np.max(np.abs(got - want), axis=-1)
    scale = np.maximum(np.max(np.abs(want), axis=-1), np.finfo(np.float64).tiny)
    err = gap / scale
    err[~np.all(np.isfinite(got), axis=-1)] = np.inf
    return err


def widest_gap(formula, A, V, dtype: str) -> float:
    """The number a control reads: the widest per-row gap of the HVPs of
    rows (A, V) computed in ``dtype`` to the float64 reference."""
    low = hvp_in(formula, A, V, dtype)
    ref = hvp_float64(formula, A, V)
    return float(row_rel_err(low, ref).max())
