"""CHESSFAD public API: chunked Hessian / Hessian-vector products.

Paper algorithm -> this module:

  Alg. 2  HESSIAN           -> hessian(..., symmetric=False)
  Alg. 3  SYM-HESSIAN       -> csize=1 special case of symmetric chunking
  Alg. 4  CHUNK-INIT        -> hdual.seed_point
  Alg. 5  CHUNK-HESS        -> hessian(..., symmetric=False)
  Alg. 6  SCHUNK-HESS       -> hessian(..., symmetric=True)
  Alg. 7  CHESS-VEC         -> hvp(..., symmetric=False)
  Alg. 8  SC-HESS-VEC       -> hvp(..., symmetric=True)
  Alg. 9  L0-HESS-VEC       -> batched_hvp(..., level="L0")
  Alg. 10 L1-HESS-VEC       -> batched_hvp(..., level="L1")
  Fig. 2  L2 CUDA kernel    -> batched_hvp(..., level="L2") and
                               kernels/chess_hvp (Pallas)

The GPU thread grid becomes vmap axes (DESIGN.md §3): on TPU, "a thread per
(instance,row,chunk)" is a batched program over those axes.  These schedules
carry the chunk as a trailing axis of each hDual component and let XLA lay
it out; the Pallas kernel (kernels/chess_hvp) puts the instances on the VPU
lanes of every component instead.

All chunk enumerations are static (numpy at trace time), so jit caches one
executable per (n, csize, symmetric) signature -- the analogue of the paper's
per-csize template instantiation.

The public functions here are thin facades over ``repro.engine``: the
engine plans csize/backend, owns the process-wide executable cache, and
dispatches to the raw schedules (`*_impl` below), which backends call
directly.  The call signatures are unchanged from the pre-engine API.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .hdual import HDual, seed_point

__all__ = [
    "eval_chunk", "hessian", "hvp", "gradient", "batched_hvp", "batched_hessian",
    "chunk_pairs", "num_chunk_evals", "optimal_csize",
    "hessian_impl", "hvp_impl", "batched_hvp_impl",
]


# ---------------------------------------------------------------------------
# chunk enumeration (static)
# ---------------------------------------------------------------------------

def _nchunk(n: int, csize: int) -> int:
    return -(-n // csize)  # ceil; the paper assumes csize | n, we allow padding


def chunk_pairs(n: int, csize: int, symmetric: bool) -> np.ndarray:
    """All (row i, chunk start) pairs to evaluate, as a (P, 2) int array.

    symmetric=True enumerates only chunks at-or-right-of the diagonal chunk
    (paper Alg. 6 line 4: startchunk = i / csize), giving
    P = n*(n/csize + 1)/2 instead of n^2/csize.
    """
    nc = _nchunk(n, csize)
    if symmetric:
        pairs = [(i, c * csize) for i in range(n) for c in range(i // csize, nc)]
    else:
        pairs = [(i, c * csize) for i in range(n) for c in range(nc)]
    return np.asarray(pairs, dtype=np.int32)


def num_chunk_evals(n: int, csize: int, symmetric: bool) -> int:
    return len(chunk_pairs(n, csize, symmetric))


def optimal_csize(n: int) -> int:
    """Paper §5: scalar multiplications of SCHUNK-HESS are minimized at
    csize = sqrt(n/2); returns the §5 model argmin over power-of-two
    divisors of n (delegates to the engine's op model)."""
    from repro.engine.opmodel import model_csize
    return model_csize(n, symmetric=True)


# ---------------------------------------------------------------------------
# single chunk evaluation
# ---------------------------------------------------------------------------

def eval_chunk(f, a, i, cstart, csize: int):
    """Evaluate one hDual pass: returns the output HDual whose ``dij`` is the
    csize-wide chunk ``H[i, cstart:cstart+csize]`` (paper Alg. 5 lines 5-10)."""
    y = seed_point(a, i, cstart, csize)
    out = f(y)
    if not isinstance(out, HDual):
        raise TypeError("CHESSFAD target function must return an HDual scalar; "
                        "write it against repro.core.hmath ops")
    return out


# ---------------------------------------------------------------------------
# full Hessian (Alg. 5 / Alg. 6)
# ---------------------------------------------------------------------------

def hessian_impl(f, a, csize: int = 1, symmetric: bool = True,
                 compute_dtype=None):
    """Raw dense-Hessian schedule (no jit -- the engine compiles/caches).

    L1 x L2 parallelism: a single vmap over the flat (row, chunk) pair list --
    every Hessian chunk is an independent program instance, exactly the
    paper's "rows are independent; chunks within a row are independent".
    ``compute_dtype`` casts the tangent sweeps (see ``hvp_impl``); the
    scatter accumulation stays in ``a.dtype``.
    """
    a = jnp.asarray(a)
    n = a.shape[-1]
    ac = a.astype(compute_dtype) if compute_dtype is not None else a
    pairs = chunk_pairs(n, csize, symmetric)
    rows = jnp.asarray(pairs[:, 0])
    starts = jnp.asarray(pairs[:, 1])

    chunks = jax.vmap(
        lambda i, c: eval_chunk(f, ac, i, c, csize).dij)(rows, starts)
    chunks = chunks.astype(a.dtype)
    # scatter chunks into the dense matrix
    cols = starts[:, None] + jnp.arange(csize)[None, :]          # (P, c)
    valid = cols < n                                              # ragged tail guard
    cols = jnp.minimum(cols, n - 1)
    rr = jnp.broadcast_to(rows[:, None], cols.shape)
    H = jnp.zeros((n, n), a.dtype)
    H = H.at[rr, cols].add(jnp.where(valid, chunks, 0.0))
    if symmetric:
        # mirror strictly-upper chunk region (paper Alg. 6 lines 14-18).
        block = (rows // csize)[:, None]
        upper = (cols // csize > block) & valid
        H = H.at[cols, rr].add(jnp.where(upper, chunks, 0.0))
    return H


# ---------------------------------------------------------------------------
# gradient (free byproduct: dj slots hold first derivatives)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 2))
def gradient(f, a, csize: int = 8):
    """Forward-mode gradient reusing the hDual machinery: one row (i=0),
    n/csize chunk sweeps; reads the ``dj`` slots (the paper notes the Jacobian
    comes out while computing the Hessian)."""
    a = jnp.asarray(a)
    n = a.shape[-1]
    nc = _nchunk(n, csize)
    starts = jnp.asarray(np.arange(nc, dtype=np.int32) * csize)
    djs = jax.vmap(lambda c: eval_chunk(f, a, 0, c, csize).dj)(starts)  # (nc, c)
    g = djs.reshape(-1)[:n]
    return g


# ---------------------------------------------------------------------------
# Hessian-vector product (Alg. 7 / Alg. 8)
# ---------------------------------------------------------------------------

def hvp_impl(f, a, v, csize: int = 1, symmetric: bool = True,
             compute_dtype=None):
    """Raw HVP schedule: r = H(a) @ v without materializing H.

    Chunks are computed, dotted against v, and discarded (paper §3.3). With
    symmetric=True the below-diagonal chunks are never evaluated; each
    strictly-above chunk element H[i,j] also contributes H[i,j]*v[i] to r[j]
    (Alg. 8 lines 12-15).

    ``compute_dtype`` runs the hDual tangent sweeps in a reduced (or
    widened) dtype -- the seed point is cast before chunk evaluation, so
    every dual component carries that dtype -- while the dot-and-scatter
    accumulation stays in ``a.dtype`` (bf16 tangents, fp32 accumulation).
    """
    a = jnp.asarray(a)
    v = jnp.asarray(v)
    n = a.shape[-1]
    acc_dt = a.dtype
    ac = a.astype(compute_dtype) if compute_dtype is not None else a
    pairs = chunk_pairs(n, csize, symmetric)
    rows = jnp.asarray(pairs[:, 0])
    starts = jnp.asarray(pairs[:, 1])

    def one(i, cstart):
        return eval_chunk(f, ac, i, cstart, csize).dij   # (c,)

    chunks = jax.vmap(one)(rows, starts).astype(acc_dt)   # (P, c)
    cols = starts[:, None] + jnp.arange(csize)[None, :]   # (P, c)
    valid = cols < n
    cols_c = jnp.minimum(cols, n - 1)
    contrib = jnp.where(valid, chunks * v[cols_c], 0.0)   # H[i,j] * v[j]
    r = jnp.zeros((n,), a.dtype).at[rows].add(contrib.sum(-1))
    if symmetric:
        block = (rows // csize)[:, None]
        upper = (cols // csize > block) & valid
        sym_contrib = jnp.where(upper, chunks * v[rows][:, None], 0.0)
        r = r.at[cols_c.reshape(-1)].add(sym_contrib.reshape(-1))
    return r


# ---------------------------------------------------------------------------
# batched instances: the paper's L0 / L1 / L2 GPU schedules (Alg. 9/10, Fig 2)
# ---------------------------------------------------------------------------

def batched_hvp_impl(f, A, V, csize: int = 1, level: str = "L2",
                     symmetric: bool = False, compute_dtype=None):
    """Raw batched-HVP schedules for m instances: A, V are (m, n).

    level="L0": one program per instance; rows+chunks sequential (lax.scan)
                inside -- mirrors Alg. 9's thread-per-instance.
    level="L1": rows also batched (vmap) -- Alg. 10's thread-per-(instance,row).
    level="L2": rows x chunks fully batched + segment reduction -- Fig. 2.

    ``compute_dtype`` runs the hDual chunk sweeps in that dtype while the
    per-row dot accumulation stays in ``A.dtype`` (see ``hvp_impl``).

    On TPU the batched axes become one flat parallel dimension; the benchmark
    suite (benchmarks/gpu_levels.py) reproduces the paper's Figs. 10-12 by
    timing the three schedules.
    """
    if level not in ("L0", "L1", "L2"):
        raise ValueError(f"unknown level {level!r}")
    A = jnp.asarray(A)
    V = jnp.asarray(V)
    n = A.shape[-1]
    acc_dt = A.dtype
    nc = _nchunk(n, csize)
    starts_np = np.arange(nc, dtype=np.int32) * csize

    if level == "L2":
        fn = partial(hvp_impl, f, csize=csize, symmetric=symmetric,
                     compute_dtype=compute_dtype)
        return jax.vmap(lambda a, v: fn(a, v))(A, V)

    Ac = A.astype(compute_dtype) if compute_dtype is not None else A

    def row_hvp(ac, v, i):
        """Sequential chunk sweep for row i (Alg. 9 inner loop)."""
        def body(res, cstart):
            dij = eval_chunk(f, ac, i, cstart, csize).dij.astype(acc_dt)
            cols = cstart + jnp.arange(csize)
            ok = cols < n
            res = res + jnp.sum(jnp.where(ok, dij * v[jnp.minimum(cols, n - 1)], 0.0))
            return res, None

        res, _ = jax.lax.scan(body, jnp.zeros((), acc_dt),
                              jnp.asarray(starts_np))
        return res

    if level == "L1":
        def inst(ac, v):
            return jax.vmap(lambda i: row_hvp(ac, v, i))(jnp.arange(n))
        return jax.vmap(inst)(Ac, V)

    # L0: rows sequential too
    def inst(ac, v):
        def body(_, i):
            return None, row_hvp(ac, v, i)
        _, out = jax.lax.scan(body, None, jnp.arange(n))
        return out

    return jax.vmap(inst)(Ac, V)


# ---------------------------------------------------------------------------
# public facades: plan/execute through the unified CurvatureEngine
# ---------------------------------------------------------------------------

def _plan(f, n, csize, symmetric, backend="auto", m=None):
    # m is a HINT ONLY (backend selection + autotune probe shaping); the
    # batch extent an executable runs at comes from the array shapes at
    # call time.  plan() rejects m=0 -- "no batching" is m=None.
    from repro.engine import plan as engine_plan
    return engine_plan(f, n, m=m, csize=csize, symmetric=symmetric,
                       backend=backend)


def hessian(f, a, csize=1, symmetric: bool = True):
    """Dense Hessian of scalar ``f`` at ``a`` (shape (n,)) via the engine's
    chunked forward-mode schedule.  csize may be an int, "auto" (§5 model)
    or "autotune" (one-shot microbenchmark)."""
    a = jnp.asarray(a)
    return _plan(f, a.shape[-1], csize, symmetric).hessian(a)


def hvp(f, a, v, csize=1, symmetric: bool = True):
    """r = H(a) @ v without materializing H (engine-planned and cached)."""
    a = jnp.asarray(a)
    return _plan(f, a.shape[-1], csize, symmetric).hvp(a, jnp.asarray(v))


def batched_hvp(f, A, V, csize=1, level: str = "L2",
                symmetric: bool = False):
    """HVPs for m instances under the paper's L0/L1/L2 schedule; the level
    maps onto the matching engine backend (vmap_l0/l1/l2).

    The batch extent is A.shape[0] -- the facade forwards it to the engine
    only as the plan's ``m`` hint (backend selection / autotune); it does
    NOT split or re-batch the arrays.  For coalescing many single-instance
    requests into batches, use ``engine.plan(...).submit`` instead."""
    if level not in ("L0", "L1", "L2"):
        raise ValueError(f"unknown level {level!r}")
    A = jnp.asarray(A)
    p = _plan(f, A.shape[-1], csize, symmetric,
              backend=f"vmap_{level.lower()}", m=A.shape[0])
    return p.batched_hvp(A, jnp.asarray(V))


def batched_hessian(f, A, csize=1, symmetric: bool = True):
    """Dense Hessians for m instances (m, n) -> (m, n, n).

    As with ``batched_hvp``, A.shape[0] is forwarded only as the plan's
    ``m`` hint; the arrays themselves define the batch."""
    A = jnp.asarray(A)
    return _plan(f, A.shape[-1], csize, symmetric,
                 m=A.shape[0]).batched_hessian(A)
