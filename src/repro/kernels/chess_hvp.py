"""chess_hvp: the paper's L2 CUDA kernel (Fig. 2), TPU-adapted in Pallas.

Paper (A100):  one CUDA thread per (instance, row, chunk); hDual components
               live in registers; per-row dot-product partials reduced via
               shared memory + __syncthreads().
Here (TPU):    grid = (instance-blocks, cells) where the trailing grid
               dimension enumerates exactly the (row, chunk) cells the
               schedule KEEPS -- ``core.api.chunk_pairs`` flattened, the
               same static enumeration the vmap schedules trace.  Each grid
               cell holds an hDual VECTOR of the whole n-variable input in
               VMEM for a block of instances, and the instances ride the
               128-lane axis in EVERY component: ``val`` and
               ``di`` are (n, width) tiles, ``dj`` and ``dij`` chunk-leading
               (csize, n, width) stacks of such tiles (``HDual(lead=True)``),
               so every overloaded op is a full-lane VPU op and a constant
               mat-vec is 2c+2 (n, width) MXU matmuls.  The wrapper
               hands the kernel variables-major (n, m) operands and
               transposes the result back.  The output block is the FULL
               padded column block (n_pad, width) whose index map ignores
               the cell grid dim, so Mosaic keeps it resident in VMEM
               across the whole cell sweep -- the paper's shared-memory
               reduction becomes a VMEM accumulator, and the symmetric
               schedule's mirrored contributions scatter into the same
               resident block.

Instances, not chunk columns, fill the lanes: with the chunk on the lanes
a component would fill csize of 128, and a block of sublane-high instance
tiles would cost a grid step per 8 instances.  The block width is a
multiple of 128 or all of m (``block_rows``), one lane tile unless
``blk_m`` asks for more; a small batch (the server's buckets) is one block
of all of m.

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
                    never match the one-hot seed so their dij slabs are
                    zero, and every in-kernel contribution is masked on
                    ``col < n``.  Any ``csize >= 1`` is served.
  m % width       : the wrapper pads the instance axis by edge replication
                    (padding rows stay inside f's domain; see
                    engine.pad_rows for the same rationale) and slices the
                    padding back off.  Any ``m >= 1`` is served; the block
                    width is a multiple of 128 or all of ``m``
                    (``block_rows``), the shapes Mosaic accepts.

The symmetric masks are CHUNK-granular, matching ``core.api.hvp_impl``
(vmap_l2) bit-for-bit in which H entries feed which output slot: a cell
strictly right of the diagonal block mirrors wholesale (H[i,j]*v[i] ->
r[j]); the diagonal-block cell contributes directly for every column,
including the below-diagonal columns inside it.

VMEM footprint of the hDual input per grid cell = n * width * (2*csize+2)
* 4B -- the paper's csize <-> fast-memory dial, verbatim, with VMEM playing
the register/L1 role (DESIGN.md §3) -- plus f's intermediates and the
(n_pad, width) resident output block.

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
    return (-(-m // block_rows(m, blk_m)), num_chunk_evals(n, csize, symmetric))


LANES = 128        # f32 lane tile: Mosaic's unit for a block's width


def block_rows(m: int, blk_m: int) -> int:
    """The instance-block width the kernel launches with.

    Instances ride the 128-lane axis, and Mosaic takes a block whose lane
    extent is a multiple of 128 or the whole (padded) instance axis, so a
    ``blk_m`` of at least ``m`` becomes ``m`` (one block) and any other
    value is rounded up to a multiple of 128 (and to ``m`` when that
    covers it).
    """
    if blk_m < 1:
        raise ValueError(f"blk_m={blk_m} must be >= 1")
    width = -(-blk_m // LANES) * LANES
    return m if width >= m else width


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


def _kernel(rows_ref, starts_ref, a_ref, v_ref, k_ref, *rest, f, n, n_pad,
            csize, width, symmetric, out_dtype):
    consts = rest[:-1]
    out_ref = rest[-1]
    t = pl.program_id(1)                       # flattened (row, chunk) cell
    i = rows_ref[t]                            # Hessian row of this cell
    cstart = starts_ref[t]                     # first column of the chunk
    first = t == 0

    at = a_ref[...].astype(jnp.float32)        # (n, width) variables-major

    # variable index of each row, loaded from a resident block: seeds built
    # from an in-kernel iota get a lane-replicated layout, and Mosaic aborts
    # when f then multiplies sublane-offset slices (x[1:] * x[:-1]) of them
    k2 = k_ref[...]                            # (n, width) int32
    di = (k2 == i).astype(jnp.float32)
    # chunk-leading seed: one (n, width) slab per chunk column.  A ragged
    # tail's columns cstart+l >= n match no variable -> zero dj slabs ->
    # zero dij slabs; the masks below drop them explicitly.
    dj = jnp.stack([(k2 == cstart + l).astype(jnp.float32)
                    for l in range(csize)])
    dij = jnp.zeros((csize, n, width), jnp.float32)

    y = HDual(at, di, dj, dij, lead=True)
    r = f(y, *[cr[...] for cr in consts])      # HDual: dij (csize, width)

    v = v_ref[...].astype(jnp.float32)         # (n_pad, width), zero-padded
    lc = jax.lax.broadcasted_iota(jnp.int32, (csize, width), 0)
    valid = cstart + lc < n
    # chunk -> padded-row one-hot: v[cstart:+csize] and v[i] are exact
    # one-hot where/sum reductions over the sublane axis (no dynamic
    # slice of the block)
    lj = jax.lax.broadcasted_iota(jnp.int32, (csize, n_pad, width), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (csize, n_pad, width), 1)
    sel = jj == cstart + lj                              # (csize, n_pad, w)
    vc = jnp.sum(jnp.where(sel, v[None], 0.0), axis=1)   # (csize, width)
    # direct: H[i, j] * v[j] -> r[i] for every valid column of the cell --
    # the compacted symmetric enumeration only ever reaches this kernel
    # with at-or-right-of-diagonal cells, and the diagonal-block cell
    # contributes ALL its columns directly (vmap_l2 semantics).
    contrib = jnp.sum(jnp.where(valid, r.dij * vc, 0.0), axis=0,
                      keepdims=True)                     # (1, width)

    rowsel = jax.lax.broadcasted_iota(jnp.int32, (n_pad, width), 0) == i
    add = jnp.where(rowsel, contrib, 0.0)                # (n_pad, width)

    if symmetric:
        # mirror: a cell strictly right of the diagonal block contributes
        # H[i, j] * v[i] to r[j] for its whole chunk (Alg. 8 lines 12-15;
        # chunk-granular like vmap_l2 -- the condition is uniform over the
        # cell because a cell spans exactly one chunk).  Scatter through the
        # chunk->row one-hot so the write stays a dense VPU op on the
        # resident output block.
        mirrors = cstart > (i // csize) * csize          # scalar, traced
        vi = jnp.sum(jnp.where(rowsel, v, 0.0), axis=0,
                     keepdims=True)                      # v[i], (1, width)
        mvals = jnp.where(valid & mirrors, r.dij, 0.0) * vi
        add = add + jnp.sum(jnp.where(sel, mvals[:, None, :], 0.0), axis=0)

    @pl.when(first)
    def _init():
        out_ref[...] = add.astype(out_dtype)

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[...] = out_ref[...] + add.astype(out_dtype)


def chess_hvp_pallas(f: Callable, A, V, csize: int, *,
                     consts: Sequence = (), blk_m: int = LANES,
                     symmetric: bool = False,
                     interpret: bool | None = None):
    """Batched HVP out[m] = H_f(A[m]) @ V[m] via the L2 grid schedule.

    A, V: (m, n). Returns (m, n).  Serves ANY (m, n, csize) with m >= 1 and
    csize >= 1: ragged tails (csize does not divide n) are masked in-kernel
    and the instance axis is padded up to a block multiple by edge
    replication.  ``symmetric=True`` launches the COMPACTED Alg. 8 grid:
    only at-or-right-of-diagonal cells exist in the trip count, and
    strictly-right cells are mirrored through the VMEM output accumulator.
    ``blk_m`` is the instance-block width (``block_rows``; one lane tile
    by default); ``kernel_grid`` is the exact launch shape.  ``interpret``
    follows ``resolve_interpret``.
    """
    from repro.core.api import chunk_pairs

    m, n = A.shape
    assert V.shape == (m, n)
    assert m >= 1 and csize >= 1, (m, csize)
    interpret = resolve_interpret(interpret)
    width = block_rows(m, blk_m)
    nchunk = -(-n // csize)                    # ceil-div chunk grid
    n_pad = nchunk * csize
    m_pad = -(-m // width) * width
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
    grid = (m_pad // width, len(pairs))
    assert grid == kernel_grid(m, n, csize, width, symmetric)
    _record_launch(m, n, csize, symmetric, width, grid)

    # instances on the lanes: the wrapper hands the kernel variables-major
    # (n, m_pad) blocks and transposes the result back.  Index maps receive
    # (mi, t, rows_ref, starts_ref): scalar-prefetch operands are appended
    # by PrefetchScalarGridSpec
    in_specs = [
        pl.BlockSpec((n, width), lambda mi, t, rs, ss: (0, mi)),      # A^T
        pl.BlockSpec((n_pad, width), lambda mi, t, rs, ss: (0, mi)),  # V^T
        pl.BlockSpec((n, width), lambda mi, t, rs, ss: (0, 0)),       # k
    ]
    for cst in consts:
        in_specs.append(
            pl.BlockSpec(cst.shape,
                         lambda mi, t, rs, ss, _nd=cst.ndim: (0,) * _nd))
    # whole-column output block, resident across the cell sweep: both the
    # per-row dot product and the symmetric mirror accumulate into it
    out_spec = pl.BlockSpec((n_pad, width), lambda mi, t, rs, ss: (0, mi))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
    )
    kernel = functools.partial(_kernel, f=f, n=n, n_pad=n_pad, csize=csize,
                               width=width, symmetric=bool(symmetric),
                               out_dtype=A.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, m_pad), A.dtype),
        interpret=interpret,
    )(rows_idx, starts_idx, A.T, V.T, jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[:, None], (n, width)), *consts)
    return out[:n, :m].T


def _record_launch(m, n, csize, symmetric, width, grid):
    """Launch-shape record in the metrics registry, written when the
    kernel is traced (once per compiled executable, never per call)."""
    from repro import obs
    reg = obs.default_registry()
    shape = f"m{m}_n{n}_c{csize}_{'sym' if symmetric else 'full'}"
    lanes = -(-width // LANES) * LANES
    for name, help_, value in (
            ("repro_chess_hvp_grid_steps", "Grid steps per kernel call.",
             grid[0] * grid[1]),
            ("repro_chess_hvp_block_width", "Instances per kernel block.",
             width),
            ("repro_chess_hvp_lanes_filled_pct",
             "Real instances over the lane slots the blocks occupy.",
             100.0 * m / (grid[0] * lanes))):
        reg.gauge(name, help_, labelnames=("shape",)).set(value, shape=shape)
    reg.counter("repro_chess_hvp_traces_total",
                "chess_hvp kernels traced (one per compiled executable).",
                labelnames=("shape",)).inc(shape=shape)
