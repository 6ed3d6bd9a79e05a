"""Mesh-distributed CHESSFAD schedules (shard_map over L0/L1/L2 axes).

The paper's GPU grid maps onto the TPU mesh as:

  L0 (instances)  -> ("pod", "data") mesh axes  (embarrassingly parallel)
  L1 (rows)       -> "model" mesh axis          (rows independent)
  L2 (chunks)     -> in-lane vector axis        (csize <= 128 per shard)

``distributed_batched_hvp`` is the production entry point used by the
batched-HVP serving example; it shards the instance batch over the data
axes.  ``distributed_hvp_rows`` / ``distributed_hessian_rows`` are the L1
row-sharded schedules behind the engine's ``sharded_rows`` backend: a
*single* large-n HVP or dense Hessian with its row blocks split over the
model axis.  Both serve ragged n (the tail rows/chunks are masked
in-shard, mirroring the kernel's in-kernel masks) and the Alg. 8 symmetric
schedule.

Symmetric scheduling (PR 6): the symmetric path now SKIPS the triangle it
discards instead of evaluating-and-masking it.  The shard's row offset is
a traced value in the SPMD program, so a per-shard *static* enumeration
cannot depend on ``axis_index`` -- instead the kept (at-or-right-of-
diagonal) cells are enumerated on the HOST (``cyclic_layout``), dealt to
shards, and shipped INTO the shard_map as a sharded index operand: every
shard sweeps only its own compacted cell list.  Row *blocks* (csize rows,
so every row in a block shares one diagonal chunk) are dealt in a
reflected round-robin ("snake") order: the block trip counts nchunk-b
form a descending sequence, and pairing block ``s`` with block
``2*size-1-s`` inside each window of ``2*size`` blocks gives every shard
the same trip total per full window -- per-shard kept-cell counts differ
by at most one block's cells (asserted in ``cyclic_layout`` and testable
through the injectable ``cell_counter``).  Under the old block layout
shard 0 owned the longest rows, so even dynamic trip counts could not
have shortened the critical path; the snake deal is what converts skipped
work into wall clock.

Collectives: the symmetric HVP psums full-length per-shard partials (the
mirror H[i,j]*v[i] -> r[j] crosses shards); the symmetric Hessian now
needs NO psum at all -- each shard all_gathers its (slots, n) block of
kept upper rows in shard-major (permuted) order, an inverse-permutation
gather restores row order, and the strictly-right-of-diagonal-block
mirror is applied locally on the replicated result (previously an
O(n^2)-sized psum).  The full schedules are collective-free beyond their
assembling all_gather, as before.

``row_layout="block"`` keeps the PR 4 evaluated-and-masked contiguous
layout (parity / benchmarking baseline); ``"cyclic"`` is the default.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from .api import batched_hvp_impl

__all__ = ["distributed_batched_hvp", "distributed_hvp_rows",
           "distributed_hessian_rows", "rows_per_shard",
           "cyclic_layout", "CyclicLayout", "snake_shard_of_block"]


def distributed_batched_hvp(mesh: Mesh, f, A, V, csize: int = 8,
                            level: str = "L2", symmetric: bool = False,
                            data_axes=("data",)):
    """L0 sharding: instances split across the data mesh axes.

    A, V: (m, n) with m divisible by the product of data-axis sizes.
    """
    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    spec = P(axes)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
             check_vma=False)
    def run(a_blk, v_blk):
        # raw schedule, not the engine facade: shard_map bodies stay
        # engine-free (the engine wraps THIS function via its sharded
        # backend and owns the jit cache one level up)
        return batched_hvp_impl(f, a_blk, v_blk, csize=csize, level=level,
                                symmetric=symmetric)

    return run(A, V)


def rows_per_shard(n: int, size: int) -> int:
    """Row-block height per model shard: ceil(n / size); the last shard's
    tail rows beyond n are dead (masked in-shard)."""
    return -(-int(n) // int(size))


# ---------------------------------------------------------------------------
# cyclic (snake) row-block layout for the symmetric triangle
# ---------------------------------------------------------------------------

def snake_shard_of_block(nblocks: int, size: int) -> np.ndarray:
    """Shard owning each chunk-block under the reflected round-robin deal.

    Blocks 0..nblocks-1 have descending symmetric trip counts nchunk-b;
    dealing each window of 2*size blocks as 0,1,..,size-1,size-1,..,1,0
    pairs block ``w*2s + s`` with ``w*2s + (2s-1-s)`` whose trips sum to a
    window constant, so full windows load every shard identically."""
    b = np.arange(int(nblocks))
    r = b % (2 * size)
    return np.where(r < size, r, 2 * size - 1 - r).astype(np.int64)


@dataclass(frozen=True)
class CyclicLayout:
    """Host-side compacted symmetric cell schedule for one (n, csize, size).

    cells[s, t] = (row, cstart, local_slot) of shard s's t-th kept cell
    (dead padding cells are clamped to (0, 0, 0) with valid False); every
    shard executes exactly ``executed`` cells, of which ``kept[s]`` are
    real.  ``row_of_slot`` / ``slot_of_row`` are the shard-major row
    permutation and its inverse (the post-all_gather restoring gather).
    """

    n: int
    csize: int
    size: int
    blocks: tuple              # per-shard owned chunk-block ids
    cells: np.ndarray          # (size, executed, 3) int32
    valid: np.ndarray          # (size, executed) bool
    kept: tuple                # per-shard real cell counts
    executed: int              # static per-shard trip count (= max kept)
    slots: int                 # local row slots per shard (all_gather width)
    row_of_slot: np.ndarray    # (size * slots,) global row, -1 dead
    slot_of_row: np.ndarray    # (n,) gathered index of each global row

    @property
    def block_cells_bound(self) -> int:
        """One block's worth of cells: the kept-count balance bound."""
        nchunk = -(-self.n // self.csize)
        return self.csize * nchunk


@functools.lru_cache(maxsize=256)
def cyclic_layout(n: int, csize: int, size: int) -> CyclicLayout:
    """Build (and memoize) the compacted snake-cyclic symmetric schedule.

    Enumerates ONLY the at-or-right-of-diagonal cells (sum over shards ==
    ``num_chunk_evals(n, csize, True)`` -- no masked ghosts), deals row
    blocks snake-cyclically, and pads every shard's list to one common
    static length.  Asserts the balance invariant: per-shard kept-cell
    counts differ by at most one block's cells."""
    n, csize, size = int(n), int(csize), int(size)
    nchunk = -(-n // csize)
    shard_of = snake_shard_of_block(nchunk, size)
    blocks = tuple(tuple(int(b) for b in np.nonzero(shard_of == s)[0])
                   for s in range(size))
    max_blocks = max(len(bs) for bs in blocks) if size else 0
    slots = max_blocks * csize

    per_shard = []
    for s in range(size):
        cs = []
        for pos, b in enumerate(blocks[s]):
            for r in range(b * csize, min((b + 1) * csize, n)):
                slot = pos * csize + (r - b * csize)
                for cc in range(b, nchunk):
                    cs.append((r, cc * csize, slot))
        per_shard.append(cs)
    kept = tuple(len(cs) for cs in per_shard)
    executed = max(kept)
    # balance invariant of the snake deal: at most one block apart
    bound = csize * nchunk
    assert max(kept) - min(kept) <= bound, (n, csize, size, kept)

    cells = np.zeros((size, executed, 3), np.int32)
    valid = np.zeros((size, executed), bool)
    for s, cs in enumerate(per_shard):
        if cs:
            cells[s, :len(cs)] = np.asarray(cs, np.int32)
            valid[s, :len(cs)] = True

    row_of_slot = np.full((size * slots,), -1, np.int64)
    slot_of_row = np.zeros((n,), np.int64)
    for s in range(size):
        for pos, b in enumerate(blocks[s]):
            for r in range(b * csize, min((b + 1) * csize, n)):
                g = s * slots + pos * csize + (r - b * csize)
                row_of_slot[g] = r
                slot_of_row[r] = g
    return CyclicLayout(n=n, csize=csize, size=size, blocks=blocks,
                        cells=cells, valid=valid, kept=kept,
                        executed=executed, slots=slots,
                        row_of_slot=row_of_slot, slot_of_row=slot_of_row)


def _count(cell_counter, layout: str, executed_per_shard, kept_per_shard):
    """Report the schedule's static cell accounting to an injected counter
    (tests / the roofline report); called once at trace/build time."""
    if cell_counter is not None:
        cell_counter({"layout": layout,
                      "executed_per_shard": list(executed_per_shard),
                      "kept_per_shard": list(kept_per_shard)})


def _cell_grid(n: int, csize: int, rows_per: int, row0):
    """Static (rows_per * nchunk) cell enumeration for one shard's row
    block, offset by the shard's (traced) first row.

    Returns (ks, rows_c, starts, cols, cols_c, valid) where ``ks`` is the
    block-local row of each cell and ``rows_c`` / ``cols_c`` are clamped
    into range so dead tail cells evaluate somewhere legal while ``valid``
    masks their contributions to zero.  (Full schedules and the legacy
    ``row_layout="block"`` symmetric parity path.)
    """
    nchunk = -(-n // csize)
    ks = jnp.repeat(jnp.arange(rows_per), nchunk)              # (P,)
    starts = jnp.tile(jnp.asarray(
        np.arange(nchunk, dtype=np.int32) * csize), rows_per)  # (P,)
    gis = row0 + ks
    rows_c = jnp.minimum(gis, n - 1)
    cols = starts[:, None] + jnp.arange(csize)[None, :]        # (P, csize)
    valid = (cols < n) & (gis < n)[:, None]
    cols_c = jnp.minimum(cols, n - 1)
    return ks, rows_c, starts, cols, cols_c, valid


def distributed_hvp_rows(mesh: Mesh, f, a, v, csize: int = 8,
                         model_axis: str = "model",
                         symmetric: bool = False,
                         row_layout: str = "cyclic",
                         cell_counter=None):
    """L1 sharding of a *single* HVP: Hessian rows split over the model axis.

    Each shard sweeps the chunks of its row block (rows are independent --
    no collective is needed for a row's own r[i]); ragged row/chunk tails
    are masked in-shard, so any (n, csize, axis size) combination is
    served.  With ``symmetric=True`` the Alg. 8 schedule runs on the
    compacted snake-cyclic cell lists (``row_layout="cyclic"``, default):
    below-diagonal cells are DROPPED from the per-shard enumeration, not
    masked, and the triangle's load is balanced to within one block per
    shard -- the symmetric sweep is ~half the full sweep's work in both
    cell count and wall clock.  The mirror H[i,j]*v[i] -> r[j] crosses row
    shards, so the symmetric path psums full-length per-shard partials;
    the full schedule assembles row blocks with an all_gather
    (``out_specs=P(model_axis)``) instead.  ``row_layout="block"`` keeps
    the PR 4 evaluated-and-masked contiguous layout as a parity baseline.
    ``cell_counter`` (injectable, tests) receives the static per-shard
    executed/kept cell counts at build time.
    """
    a = jnp.asarray(a)
    v = jnp.asarray(v)
    n = a.shape[-1]
    size = mesh.shape[model_axis]
    rows_per = rows_per_shard(n, size)
    nchunk = -(-n // csize)

    def cell(a_rep, i, cstart):
        from .api import eval_chunk
        return eval_chunk(f, a_rep, i, cstart, csize).dij      # (csize,)

    if not symmetric:
        _count(cell_counter, "block", [rows_per * nchunk] * size,
               [rows_per * nchunk] * size)

        @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=P(model_axis), check_vma=False)
        def run(a_rep, v_rep):
            row0 = jax.lax.axis_index(model_axis) * rows_per
            ks, rows_c, starts, _cols, cols_c, valid = _cell_grid(
                n, csize, rows_per, row0)
            chunks = jax.vmap(lambda i, c: cell(a_rep, i, c))(rows_c, starts)
            contrib = jnp.where(valid, chunks * v_rep[cols_c], 0.0)
            r_blk = jnp.zeros((rows_per,), a_rep.dtype)
            return r_blk.at[ks].add(contrib.sum(-1))

        return run(a, v)[:n]

    if row_layout == "block":
        # PR 4 parity baseline: contiguous row blocks, below-diagonal cells
        # evaluated-and-masked (the SPMD grid offset is traced, so a static
        # in-shard grid must stay nchunk wide)
        _count(cell_counter, "block", [rows_per * nchunk] * size,
               [rows_per * nchunk] * size)

        @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                 check_vma=False)
        def run_sym_block(a_rep, v_rep):
            row0 = jax.lax.axis_index(model_axis) * rows_per
            _ks, rows_c, starts, cols, cols_c, valid = _cell_grid(
                n, csize, rows_per, row0)
            chunks = jax.vmap(lambda i, c: cell(a_rep, i, c))(rows_c, starts)
            block = (rows_c // csize)[:, None]
            at_or_right = (cols // csize) >= block
            direct = jnp.where(valid & at_or_right,
                               chunks * v_rep[cols_c], 0.0)
            r = jnp.zeros((n,), a_rep.dtype).at[rows_c].add(direct.sum(-1))
            upper = ((cols // csize) > block) & valid
            mirror = jnp.where(upper, chunks * v_rep[rows_c][:, None], 0.0)
            r = r.at[cols_c.reshape(-1)].add(mirror.reshape(-1))
            return jax.lax.psum(r, model_axis)

        return run_sym_block(a, v)
    if row_layout != "cyclic":
        raise ValueError(f"unknown row_layout {row_layout!r}; "
                         "expected 'cyclic' or 'block'")

    lay = cyclic_layout(n, csize, size)
    _count(cell_counter, "cyclic", [lay.executed] * size, lay.kept)
    cells_op = jnp.asarray(lay.cells)          # (size, executed, 3)
    valid_op = jnp.asarray(lay.valid)          # (size, executed)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(), P(model_axis), P(model_axis)),
             out_specs=P(), check_vma=False)
    def run_sym(a_rep, v_rep, cells_blk, valid_blk):
        rows = cells_blk[0, :, 0]              # this shard's kept cells
        starts = cells_blk[0, :, 1]
        chunks = jax.vmap(lambda i, c: cell(a_rep, i, c))(rows, starts)
        cols = starts[:, None] + jnp.arange(csize)[None, :]
        valid = valid_blk[0][:, None] & (cols < n)
        cols_c = jnp.minimum(cols, n - 1)
        direct = jnp.where(valid, chunks * v_rep[cols_c], 0.0)
        r = jnp.zeros((n,), a_rep.dtype).at[rows].add(direct.sum(-1))
        # cells strictly right of their row's diagonal block mirror
        # wholesale (chunk-granular, vmap_l2 semantics)
        mirrors = starts > (rows // csize) * csize
        mirror = jnp.where(valid & mirrors[:, None],
                           chunks * v_rep[rows][:, None], 0.0)
        r = r.at[cols_c.reshape(-1)].add(mirror.reshape(-1))
        return jax.lax.psum(r, model_axis)

    return run_sym(a, v, cells_op, valid_op)


def distributed_hessian_rows(mesh: Mesh, f, a, csize: int = 8,
                             model_axis: str = "model",
                             symmetric: bool = False,
                             row_layout: str = "cyclic",
                             cell_counter=None):
    """L1 sharding of a *single* dense Hessian: each model shard fills its
    row block of H.

    The full schedule stacks the per-shard (rows_per, n) blocks with an
    all_gather.  The symmetric schedule (``row_layout="cyclic"``, default)
    evaluates ONLY the kept at-or-right-of-diagonal cells of its
    snake-dealt row blocks, all_gathers the (slots, n) upper blocks in
    shard-major (permuted) row order, restores row order with an
    inverse-permutation gather, and applies the strictly-right-of-
    diagonal-block mirror LOCALLY on the replicated result -- no psum (the
    PR 4 path all-reduced full (n, n) partials).  ``row_layout="block"``
    keeps that psum path as a parity baseline.
    """
    a = jnp.asarray(a)
    n = a.shape[-1]
    size = mesh.shape[model_axis]
    rows_per = rows_per_shard(n, size)
    nchunk = -(-n // csize)

    def cell(a_rep, i, cstart):
        from .api import eval_chunk
        return eval_chunk(f, a_rep, i, cstart, csize).dij

    if not symmetric:
        _count(cell_counter, "block", [rows_per * nchunk] * size,
               [rows_per * nchunk] * size)

        @partial(shard_map, mesh=mesh, in_specs=(P(),),
                 out_specs=P(model_axis), check_vma=False)
        def run(a_rep):
            row0 = jax.lax.axis_index(model_axis) * rows_per
            ks, rows_c, starts, _cols, cols_c, valid = _cell_grid(
                n, csize, rows_per, row0)
            chunks = jax.vmap(lambda i, c: cell(a_rep, i, c))(rows_c, starts)
            blk = jnp.zeros((rows_per, n), a_rep.dtype)
            kk = jnp.broadcast_to(ks[:, None], cols_c.shape)
            return blk.at[kk, cols_c].add(jnp.where(valid, chunks, 0.0))

        return run(a)[:n]

    if row_layout == "block":
        _count(cell_counter, "block", [rows_per * nchunk] * size,
               [rows_per * nchunk] * size)

        @partial(shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
                 check_vma=False)
        def run_sym_block(a_rep):
            row0 = jax.lax.axis_index(model_axis) * rows_per
            _ks, rows_c, starts, cols, cols_c, valid = _cell_grid(
                n, csize, rows_per, row0)
            chunks = jax.vmap(lambda i, c: cell(a_rep, i, c))(rows_c, starts)
            block = (rows_c // csize)[:, None]
            at_or_right = (cols // csize) >= block
            rr = jnp.broadcast_to(rows_c[:, None], cols_c.shape)
            H = jnp.zeros((n, n), a_rep.dtype)
            H = H.at[rr, cols_c].add(
                jnp.where(valid & at_or_right, chunks, 0.0))
            upper = ((cols // csize) > block) & valid
            H = H.at[cols_c, rr].add(jnp.where(upper, chunks, 0.0))
            return jax.lax.psum(H, model_axis)

        return run_sym_block(a)
    if row_layout != "cyclic":
        raise ValueError(f"unknown row_layout {row_layout!r}; "
                         "expected 'cyclic' or 'block'")

    lay = cyclic_layout(n, csize, size)
    _count(cell_counter, "cyclic", [lay.executed] * size, lay.kept)
    cells_op = jnp.asarray(lay.cells)
    valid_op = jnp.asarray(lay.valid)
    slots = lay.slots

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(model_axis), P(model_axis)),
             out_specs=P(model_axis), check_vma=False)
    def upper_blocks(a_rep, cells_blk, valid_blk):
        rows = cells_blk[0, :, 0]
        starts = cells_blk[0, :, 1]
        slot = cells_blk[0, :, 2]
        chunks = jax.vmap(lambda i, c: cell(a_rep, i, c))(rows, starts)
        cols = starts[:, None] + jnp.arange(csize)[None, :]
        valid = valid_blk[0][:, None] & (cols < n)
        cols_c = jnp.minimum(cols, n - 1)
        blk = jnp.zeros((slots, n), a_rep.dtype)
        kk = jnp.broadcast_to(slot[:, None], cols_c.shape)
        return blk.at[kk, cols_c].add(jnp.where(valid, chunks, 0.0))

    # shard-major permuted kept-row blocks -> restore row order with the
    # inverse-permutation gather, then mirror locally (replicated, no psum)
    U_perm = upper_blocks(a, cells_op, valid_op)         # (size*slots, n)
    U = U_perm[jnp.asarray(lay.slot_of_row)]             # (n, n) row-ordered
    bi = np.arange(n) // csize
    strictly_right = jnp.asarray(bi[None, :] > bi[:, None])
    return U + jnp.where(strictly_right, U, 0.0).T
