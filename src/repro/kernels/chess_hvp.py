"""chess_hvp: the paper's L2 CUDA kernel (Fig. 2), TPU-adapted in Pallas.

Paper (A100):  one CUDA thread per (instance, row, chunk); hDual components
               live in registers; per-row dot-product partials reduced via
               shared memory + __syncthreads().
Here (TPU):    grid = (instance-blocks, cells) where the trailing grid
               dimension enumerates exactly the (row, chunk) cells the
               schedule KEEPS -- ``core.api.chunk_pairs`` flattened, the
               same static enumeration the vmap schedules trace.  Each grid
               cell holds an hDual VECTOR of the whole n-variable input in
               VMEM with a trailing csize chunk axis (lane-vectorized on
               the VPU) and a block of instances on the sublane axis.  The
               output block is the FULL padded row vector (blk_m, n_pad)
               whose index map ignores the cell grid dim, so Mosaic keeps
               it resident in VMEM across the whole cell sweep -- the
               paper's shared-memory reduction becomes a VMEM accumulator,
               and the symmetric schedule's mirrored contributions scatter
               into the same resident block.

Kernel v3 (PR 6) makes the symmetric schedule TRULY skip: v2 launched the
full (rows x chunks) L2 grid and predicated below-diagonal cells with
``pl.when`` -- half the grid still issued, paying grid/DMA overhead per
skipped cell, so the "~half the tangent sweeps" never showed up as wall
clock.  v3 compacts the grid instead: the trailing grid dimension is the
flattened upper-triangular cell enumeration (Alg. 8 line 4: row i's chunks
start at ``i // csize``), delivered to the kernel as two scalar-prefetch
index vectors ``rows[t]`` / ``starts[t]`` (SMEM on TPU).  Below-diagonal
cells are never launched; the grid trip count IS the tangent-sweep count:

  cells(symmetric=False) = n * ceil(n/csize)
  cells(symmetric=True)  = sum_i (ceil(n/csize) - i // csize)
                         = csize * nchunk * (nchunk+1) / 2   when csize | n

``kernel_grid`` exposes that static launch shape as the sweep-count
witness tests and the roofline report assert against.

v2's lifted preconditions are kept verbatim:

  ragged tails    : the chunk grid is ceil(n / csize); seed columns past n
                    never match the one-hot iota so their dij lanes are
                    zero, and every in-kernel contribution is masked on
                    ``col < n``.  Any ``csize >= 1`` is served.
  m % blk_m       : the wrapper pads the instance axis by edge replication
                    (padding rows stay inside f's domain; see
                    engine.pad_rows for the same rationale) and slices the
                    padding back off.  Any ``m >= 1`` is served; the block
                    height is a multiple of 8 or all of ``m``
                    (``block_rows``), the shapes Mosaic accepts.

The symmetric masks are CHUNK-granular, matching ``core.api.hvp_impl``
(vmap_l2) bit-for-bit in which H entries feed which output slot: a cell
strictly right of the diagonal block mirrors wholesale (H[i,j]*v[i] ->
r[j]); the diagonal-block cell contributes directly for every column,
including the below-diagonal columns inside it.

VMEM footprint per grid cell = n * blk_m * (2*csize + 2) * 4B -- the paper's
csize <-> fast-memory dial, verbatim, with VMEM playing the register/L1
role (DESIGN.md §3) -- plus the (blk_m, n_pad) resident output row block.

The kernel is generic over any ``f`` written against repro.core.hmath /
HDual ops (trace-time polymorphism = the paper's template instantiation);
constant coefficient arrays (Fletcher-Powell's A, B, E) enter as extra refs
broadcast to every grid cell.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hdual import HDual

__all__ = ["chess_hvp_pallas", "kernel_grid", "block_rows",
           "resolve_interpret"]


def kernel_grid(m: int, n: int, csize: int, blk_m: int,
                symmetric: bool) -> tuple[int, int]:
    """Static launch grid (instance blocks, chunk cells) of the kernel.

    The trailing extent is EXACTLY the number of second-order tangent
    sweeps the kernel executes -- the compacted symmetric grid enumerates
    only at-or-right-of-diagonal cells, so there are no predicated ghost
    cells to subtract.  This is the sweep-count witness the parity tests
    and the roofline report assert against ``core.api.num_chunk_evals``.
    """
    from repro.core.api import num_chunk_evals
    blk_m = block_rows(m, blk_m)
    m_pad = -(-m // blk_m) * blk_m
    return (m_pad // blk_m, num_chunk_evals(n, csize, symmetric))


SUBLANES = 8      # f32 sublane tile: Mosaic's unit for a block's row count


def block_rows(m: int, blk_m: int) -> int:
    """The instance-block height the kernel launches with.

    Mosaic takes a block whose row count is a multiple of the sublane tile
    or the whole (padded) instance axis, so a ``blk_m`` of at least ``m``
    becomes ``m`` (one block) and any other value is rounded up to a
    multiple of 8.
    """
    if blk_m < 1:
        raise ValueError(f"blk_m={blk_m} must be >= 1")
    return min(m, -(-blk_m // SUBLANES) * SUBLANES)


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode for a Pallas call: the default off the TPU, refused
    on it, where every kernel compiles with Mosaic."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is refused on the TPU: "
                         "kernels there compile with Mosaic")
    return bool(interpret)


def _kernel(rows_ref, starts_ref, a_ref, v_ref, *rest, f, n, n_pad, csize,
            blk_m, symmetric, out_dtype):
    consts = rest[:-1]
    out_ref = rest[-1]
    t = pl.program_id(1)                       # flattened (row, chunk) cell
    i = rows_ref[t]                            # Hessian row of this cell
    cstart = starts_ref[t]                     # first column of the chunk
    first = t == 0

    a = a_ref[...].astype(jnp.float32)         # (blk_m, n)
    at = a.T                                   # (n, blk_m) variables-major

    k2 = jax.lax.broadcasted_iota(jnp.int32, (n, blk_m), 0)
    di = (k2 == i).astype(jnp.float32)
    l2 = jax.lax.broadcasted_iota(jnp.int32, (blk_m, csize), 1)
    # ragged tail: columns cstart+l >= n match no variable -> zero dj
    # lanes -> zero dij lanes; the masks below drop them explicitly.  Built
    # from 2-D iotas: Mosaic aborts on leading-axis slices (f's x[1:]) of
    # a 3-D iota once blk_m spans more than one sublane tile.
    dj = (k2[:, :, None] == cstart + l2[None, :, :]).astype(jnp.float32)
    dij = jnp.zeros((n, blk_m, csize), jnp.float32)

    y = HDual(at, di, dj, dij)
    r = f(y, *[cr[...] for cr in consts])      # HDual: dij (blk_m, csize)

    v = v_ref[...].astype(jnp.float32)         # (blk_m, n_pad), zero-padded
    cols = cstart + jax.lax.broadcasted_iota(jnp.int32, (blk_m, csize), 1)
    valid = cols < n
    # chunk -> padded-column one-hot: the lane gathers v[:, cstart:+csize]
    # and v[:, i] are exact one-hot where/sum reductions, because Mosaic
    # has no lowering for a dynamic lane gather (take_along_axis)
    lj = jax.lax.broadcasted_iota(jnp.int32, (csize, n_pad), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (csize, n_pad), 1)
    sel = jj == cstart + lj                              # (csize, n_pad)
    vc = jnp.sum(jnp.where(sel[None, :, :], v[:, None, :], 0.0), axis=2)
    # direct: H[i, j] * v[j] -> r[i] for every valid column of the cell --
    # the compacted symmetric enumeration only ever reaches this kernel
    # with at-or-right-of-diagonal cells, and the diagonal-block cell
    # contributes ALL its columns directly (vmap_l2 semantics).
    contrib = jnp.sum(jnp.where(valid, r.dij * vc, 0.0), axis=1,
                      keepdims=True)                     # (blk_m, 1)

    rowsel = jax.lax.broadcasted_iota(jnp.int32, (blk_m, n_pad), 1) == i
    add = jnp.where(rowsel, contrib, 0.0)               # (blk_m, n_pad)

    if symmetric:
        # mirror: a cell strictly right of the diagonal block contributes
        # H[i, j] * v[i] to r[j] for its whole chunk (Alg. 8 lines 12-15;
        # chunk-granular like vmap_l2 -- the condition is uniform over the
        # cell because a cell spans exactly one chunk).  Scatter through the
        # chunk->row one-hot so the write stays a dense VPU op on the
        # resident output block.
        mirrors = cstart > (i // csize) * csize          # scalar, traced
        vi = jnp.sum(jnp.where(rowsel, v, 0.0), axis=1,
                     keepdims=True)                      # v[:, i]
        mvals = jnp.where(valid & mirrors, r.dij, 0.0) * vi
        add = add + jnp.sum(
            jnp.where(sel[None, :, :], mvals[:, :, None], 0.0), axis=1)

    @pl.when(first)
    def _init():
        out_ref[...] = add.astype(out_dtype)

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[...] = out_ref[...] + add.astype(out_dtype)


def chess_hvp_pallas(f: Callable, A, V, csize: int, *,
                     consts: Sequence = (), blk_m: int = 8,
                     symmetric: bool = False,
                     interpret: bool | None = None):
    """Batched HVP out[m] = H_f(A[m]) @ V[m] via the L2 grid schedule.

    A, V: (m, n). Returns (m, n).  Serves ANY (m, n, csize) with m >= 1 and
    csize >= 1: ragged tails (csize does not divide n) are masked in-kernel
    and the instance axis is padded up to a blk_m multiple by edge
    replication (v2; the seed kernel required csize | n and m % blk_m == 0).
    ``symmetric=True`` launches the COMPACTED Alg. 8 grid: only
    at-or-right-of-diagonal cells exist in the trip count (v3 -- no
    predicated ghosts), and strictly-right cells are mirrored through the
    VMEM output accumulator.  ``kernel_grid(m, n, csize, blk_m, symmetric)``
    is the exact launch shape; ``block_rows`` says which block height a
    requested ``blk_m`` becomes.  ``interpret`` follows
    ``resolve_interpret``.
    """
    from repro.core.api import chunk_pairs

    m, n = A.shape
    assert V.shape == (m, n)
    assert m >= 1 and csize >= 1, (m, csize)
    interpret = resolve_interpret(interpret)
    blk_m = block_rows(m, blk_m)
    nchunk = -(-n // csize)                    # ceil-div chunk grid
    n_pad = nchunk * csize
    m_pad = -(-m // blk_m) * blk_m
    if m_pad != m:
        # edge replication keeps padded instances inside f's domain (e.g.
        # Ackley's sqrt is non-differentiable at the zero vector)
        A = jnp.concatenate(
            [A, jnp.broadcast_to(A[-1:], (m_pad - m, n))], axis=0)
        V = jnp.concatenate(
            [V, jnp.broadcast_to(V[-1:], (m_pad - m, n))], axis=0)
    if n_pad != n:
        # only V is padded (zeros beyond n never contribute); A keeps the
        # true n so f sees the real evaluation point
        V = jnp.concatenate(
            [V, jnp.zeros((m_pad, n_pad - n), V.dtype)], axis=1)

    # the schedule's kept cells, flattened: the SAME static enumeration the
    # vmap schedules trace (core.api.chunk_pairs), shipped as two scalar-
    # prefetch index vectors (SMEM on TPU, available before the body runs)
    pairs = chunk_pairs(n, csize, symmetric)             # (P, 2) numpy
    rows_idx = jnp.asarray(pairs[:, 0])
    starts_idx = jnp.asarray(pairs[:, 1])
    grid = (m_pad // blk_m, len(pairs))
    assert grid == kernel_grid(m, n, csize, blk_m, symmetric)

    # index maps receive (mi, t, rows_ref, starts_ref): scalar-prefetch
    # operands are appended by PrefetchScalarGridSpec
    in_specs = [
        pl.BlockSpec((blk_m, n), lambda mi, t, rs, ss: (mi, 0)),      # A
        pl.BlockSpec((blk_m, n_pad), lambda mi, t, rs, ss: (mi, 0)),  # V
    ]
    for cst in consts:
        in_specs.append(
            pl.BlockSpec(cst.shape,
                         lambda mi, t, rs, ss, _nd=cst.ndim: (0,) * _nd))
    # full-row output block, resident across the cell sweep: both the
    # per-row dot product and the symmetric mirror accumulate into it
    out_spec = pl.BlockSpec((blk_m, n_pad), lambda mi, t, rs, ss: (mi, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
    )
    kernel = functools.partial(_kernel, f=f, n=n, n_pad=n_pad, csize=csize,
                               blk_m=blk_m, symmetric=bool(symmetric),
                               out_dtype=A.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), A.dtype),
        interpret=interpret,
    )(rows_idx, starts_idx, A, V, *consts)
    return out[:m, :n]
