"""Pallas kernel sweeps (interpret mode on CPU): shapes x dtypes x csize
against the pure-jnp oracles in kernels/ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import testfns
from repro.kernels.chess_hvp import block_rows, resolve_interpret
from repro.kernels.ops import (_fn_and_consts, chess_hvp, hdual_linear,
                               hdual_linear_apply)
from repro.kernels.ref import chess_hvp_ref, hdual_linear_ref


@pytest.mark.parametrize("function",
                         ["rosenbrock", "ackley", "fletcher_powell"])
@pytest.mark.parametrize("m,n,csize,blk_m", [
    (16, 8, 2, 8), (8, 16, 4, 4), (8, 8, 8, 8), (24, 12, 3, 8),
])
def test_chess_hvp_sweep(function, m, n, csize, blk_m):
    rng = np.random.RandomState(m * 31 + n)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    out = chess_hvp(A, V, function=function, csize=csize, blk_m=blk_m)
    f, consts = _fn_and_consts(function, n)
    want = chess_hvp_ref(f, A, V, csize, consts)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want),
        rtol=5e-3, atol=5e-3 * (1 + np.abs(np.asarray(want)).max()))


# ---------------------------------------------------------------------------
# kernel v2: ragged tails, symmetric schedule, instance padding (PR 3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("function",
                         ["rosenbrock", "ackley", "fletcher_powell"])
@pytest.mark.parametrize("m,n,csize,blk_m", [
    (8, 10, 4, 8),     # ragged: 10 % 4 != 0
    (8, 9, 2, 4),      # ragged odd n
    (5, 8, 2, 8),      # m % blk_m != 0 (padded to one 5-row block)
    (13, 7, 3, 4),     # ragged n AND ragged m
    (4, 6, 16, 8),     # csize > n (single over-wide chunk)
])
@pytest.mark.parametrize("symmetric", [False, True])
def test_chess_hvp_v2_sweep(function, m, n, csize, blk_m, symmetric):
    """No csize | n or m % blk_m precondition remains: any flat batched_hvp
    the vmap backends serve, the kernel serves, on both schedules."""
    rng = np.random.RandomState(m * 131 + n + csize)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    out = chess_hvp(A, V, function=function, csize=csize, blk_m=blk_m,
                    symmetric=symmetric)
    f, consts = _fn_and_consts(function, n)
    want = chess_hvp_ref(f, A, V, csize, consts)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want),
        rtol=5e-3, atol=5e-3 * (1 + np.abs(np.asarray(want)).max()))


@pytest.mark.parametrize("function",
                         ["rosenbrock", "ackley", "fletcher_powell"])
def test_symmetric_schedule_matches_vmap_l2(function):
    """Acceptance: the kernel's symmetric schedule agrees with vmap_l2
    (fp32 tolerance) on every registered test function."""
    from repro import engine
    m, n, csize = 8, 10, 4
    rng = np.random.RandomState(17)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    f = testfns.FUNCTIONS[function](n)
    p_pl = engine.plan(f, n, m=m, csize=csize, backend="pallas",
                       symmetric=True)
    p_l2 = engine.plan(f, n, m=m, csize=csize, backend="vmap_l2",
                       symmetric=True)
    got = np.asarray(p_pl.batched_hvp(A, V))
    want = np.asarray(p_l2.batched_hvp(A, V))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * (1 + np.abs(want).max()))


# ---------------------------------------------------------------------------
# kernel v3: compacted symmetric grid -- sweep-count witness + parity (PR 6)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,csize", [(16, 4), (12, 4), (13, 4), (9, 2),
                                     (8, 8), (6, 16)])
def test_sweep_count_witness(n, csize):
    """The launch grid's trailing extent IS the tangent-sweep count: the
    compacted symmetric grid enumerates exactly the upper-triangle chunk
    cells -- csize * nchunk * (nchunk+1) / 2 when csize | n -- with no
    predicated ghost cells (v2 launched the full grid and masked)."""
    from repro.core.api import chunk_pairs, num_chunk_evals
    from repro.kernels.chess_hvp import kernel_grid

    nchunk = -(-n // csize)
    sym = kernel_grid(8, n, csize, 8, True)
    full = kernel_grid(8, n, csize, 8, False)
    assert full[1] == n * nchunk
    assert sym[1] == num_chunk_evals(n, csize, True)
    assert sym[1] == len(chunk_pairs(n, csize, True))
    if n % csize == 0:
        assert sym[1] == csize * nchunk * (nchunk + 1) // 2
    if nchunk > 1:
        assert sym[1] < full[1]
    # every enumerated cell is at-or-right of its row's diagonal block
    pairs = chunk_pairs(n, csize, True)
    assert all(c >= (r // csize) * csize for r, c in pairs)


@pytest.mark.parametrize("function",
                         ["rosenbrock", "ackley", "fletcher_powell"])
@pytest.mark.parametrize("n", [8, 10])
@pytest.mark.parametrize("m,blk_m", [(1, 128), (140, 128)])
def test_compacted_sym_parity_vs_oracle(function, n, m, blk_m):
    """Compacted-grid symmetric parity against the fwd-fwd oracle on all
    testfns x {divisible, ragged n} x {m=1, m > blk_m} (PR 6 satellite)."""
    rng = np.random.RandomState(m * 7 + n)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    out = chess_hvp(A, V, function=function, csize=4, blk_m=blk_m,
                    symmetric=True)
    f, consts = _fn_and_consts(function, n)
    want = chess_hvp_ref(f, A, V, 4, consts)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want),
        rtol=5e-3, atol=5e-3 * (1 + np.abs(np.asarray(want)).max()))


def test_symmetric_vs_full_schedules_agree():
    """Both schedules compute the same HVP (the symmetric one touching
    roughly half the chunks)."""
    m, n, csize = 6, 12, 4
    rng = np.random.RandomState(5)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    full = chess_hvp(A, V, function="rosenbrock", csize=csize, blk_m=4,
                     symmetric=False)
    sym = chess_hvp(A, V, function="rosenbrock", csize=csize, blk_m=4,
                    symmetric=True)
    np.testing.assert_allclose(np.asarray(sym), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_instance_padding_is_invisible():
    """Padding rows (edge-replicated to stay in f's domain) must not leak
    into real outputs: m=129 with 128-wide blocks equals the same rows
    computed unpadded."""
    n, csize = 8, 4
    rng = np.random.RandomState(23)
    A = jnp.asarray(rng.uniform(-2, 2, (129, n)), jnp.float32)
    V = jnp.asarray(rng.randn(129, n), jnp.float32)
    padded = chess_hvp(A, V, function="ackley", csize=csize, blk_m=128)
    exact = chess_hvp(A[:128], V[:128], function="ackley", csize=csize,
                      blk_m=128)
    np.testing.assert_allclose(np.asarray(padded[:128]), np.asarray(exact),
                               rtol=1e-6, atol=1e-6)
    assert padded.shape == (129, n)


def test_chess_hvp_matches_jax_hessian():
    """End-to-end: kernel output == H @ v with H from jax.hessian."""
    from repro.core import testfns
    m, n, csize = 8, 8, 4
    rng = np.random.RandomState(7)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    out = chess_hvp(A, V, function="rosenbrock", csize=csize, blk_m=8)
    H = jax.vmap(jax.hessian(testfns.rosenbrock))(A)
    want = jnp.einsum("mij,mj->mi", H, V)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K2,T,din,dout,bt,bo,bk", [
    (6, 32, 16, 24, 32, 8, 16),
    (10, 128, 128, 128, 64, 128, 32),
    (4, 64, 32, 128, 16, 64, 32),
    (18, 8, 8, 8, 8, 8, 8),
])
def test_hdual_linear_sweep(dtype, K2, T, din, dout, bt, bo, bk):
    rng = np.random.RandomState(K2)
    x = jnp.asarray(rng.randn(K2, T, din), dtype)
    w = jnp.asarray(rng.randn(din, dout), dtype)
    out = hdual_linear(x, w, bt=bt, bo=bo, bk=bk)
    want = hdual_linear_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * din)


def test_hdual_linear_apply_equals_matvec_const():
    import repro.core.hmath as hm
    from repro.core.hdual import seed_point

    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(16), jnp.float32)
    W = jnp.asarray(rng.randn(16, 8), jnp.float32)
    y = seed_point(a, 3, 4, 4)
    want = hm.matvec_const(W.T, y)
    got = hdual_linear_apply(y, W, bt=16, bo=8, bk=16)
    for nm in ("val", "di", "dj", "dij"):
        np.testing.assert_allclose(np.asarray(getattr(got, nm)),
                                   np.asarray(getattr(want, nm)),
                                   rtol=1e-5, atol=1e-5)


def test_hdual_linear_second_derivative_through_network():
    """Push hDuals through linear->sin->linear with the fused kernel and
    check the Hessian chunk against jax.hessian."""
    import repro.core.hmath as hm
    from repro.core.hdual import seed_point

    rng = np.random.RandomState(11)
    n, h = 8, 16
    W1 = jnp.asarray(rng.randn(n, h) / np.sqrt(n), jnp.float32)
    W2 = jnp.asarray(rng.randn(h, 1) / np.sqrt(h), jnp.float32)

    def net_jnp(x):
        return jnp.sin(x @ W1).sum() + (jnp.sin(x @ W1) @ W2)[0]

    a = jnp.asarray(rng.randn(n), jnp.float32)
    csize = 4
    y = seed_point(a, 2, 0, csize)
    hidden = hm.sin(hdual_linear_apply(y, W1, bt=8, bo=8, bk=8))
    out = hidden.sum(0) + hdual_linear_apply(hidden, W2, bt=8, bo=1,
                                             bk=8)[0]
    H = jax.hessian(net_jnp)(a)
    np.testing.assert_allclose(np.asarray(out.dij),
                               np.asarray(H[2, :csize]), rtol=1e-3,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# kernel v4: instances on the lanes, lane-wide instance blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("function,m,n,csize,blk_m,symmetric", [
    ("rosenbrock", 300, 16, 2, 128, True),       # 3 lane blocks, m ragged
    ("fletcher_powell", 300, 16, 2, 128, False),
    ("ackley", 300, 10, 4, 256, True),           # ragged n, 2 lane blocks
    ("fletcher_powell", 130, 9, 4, 128, True),   # ragged n and m
    ("rosenbrock", 40, 7, 3, 128, False),        # m < one lane tile
])
def test_lane_blocks_match_vmap_l2_and_oracle(function, m, n, csize, blk_m,
                                              symmetric):
    """Several 128-lane instance blocks, a ragged last block and ragged
    chunks agree with the vmap_l2 schedule and the fwd-fwd oracle."""
    from repro import engine
    from repro.kernels.chess_hvp import kernel_grid
    rng = np.random.RandomState(m + n)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    out = np.asarray(chess_hvp(A, V, function=function, csize=csize,
                               blk_m=blk_m, symmetric=symmetric))
    f = testfns.FUNCTIONS[function](n)
    l2 = engine.plan(f, n, m=m, csize=csize, backend="vmap_l2",
                     symmetric=symmetric)
    want = np.asarray(l2.batched_hvp(A, V))
    np.testing.assert_allclose(out, want, rtol=1e-5,
                               atol=1e-5 * (1 + np.abs(want).max()))
    kf, consts = _fn_and_consts(function, n)
    oracle = np.asarray(chess_hvp_ref(kf, A, V, csize, consts))
    np.testing.assert_allclose(out, oracle, rtol=5e-3,
                               atol=5e-3 * (1 + np.abs(oracle).max()))
    blocks = kernel_grid(m, n, csize, blk_m, symmetric)[0]
    assert blocks == -(-m // block_rows(m, blk_m)) == -(-m // blk_m)


def _mixed_rank(x):
    """Mixes ranks the way user code does: a row, a reduction and a
    branch of lower rank meet the whole vector."""
    import repro.core.hmath as hm
    s = x.sum(0)
    y = x * x[0] + hm.where(x[0] > 0.0, x, s) * s
    return (y * y).sum(0)


@pytest.mark.parametrize("n,csize,symmetric", [
    (4, 4, True), (4, 4, False),     # csize == n: c would line up with n
    (6, 4, True),                    # ragged chunks
])
def test_mixed_rank_f_matches_vmap_l2_and_jax_hessian(n, csize, symmetric):
    """An f whose operands differ in rank gives the same HVPs on the
    kernel's chunk-leading layout as on the vmap schedules."""
    from repro import engine
    m = 130
    rng = np.random.RandomState(n + csize)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    got = {}
    for backend in ("pallas", "vmap_l2"):
        p = engine.plan(_mixed_rank, n, m=m, csize=csize, backend=backend,
                        symmetric=symmetric, blk_m=128)
        got[backend] = np.asarray(p.batched_hvp(A, V))
    want = np.asarray(jax.vmap(
        lambda a, v: jax.hessian(_mixed_rank)(a) @ v)(A, V))
    scale = 1 + np.abs(want).max()
    np.testing.assert_allclose(got["pallas"], got["vmap_l2"], rtol=1e-5,
                               atol=1e-5 * scale)
    np.testing.assert_allclose(got["pallas"], want, rtol=1e-4,
                               atol=1e-4 * scale)


def test_launch_shape_recorded_once_per_compiled_plan():
    """Building a pallas executable records its launch shape once in the
    metrics registry (at trace time); calling it again records nothing."""
    from repro import engine, obs
    from repro.kernels.chess_hvp import kernel_grid
    obs.reset()
    m, n = 300, 8
    p = engine.plan(testfns.rosenbrock, n, m=m, csize=2, backend="pallas",
                    symmetric=True, blk_m=128)
    A, V = (jnp.ones((m, n), jnp.float32),) * 2
    for _ in range(3):
        jax.block_until_ready(p.batched_hvp(A, V))
    reg = obs.metrics_registry()
    shape = f"m{m}_n{n}_c2_sym"
    blocks, cells = kernel_grid(m, n, 2, 128, True)
    assert reg.value("repro_chess_hvp_traces_total", shape=shape) == 1
    assert reg.value("repro_chess_hvp_grid_steps", shape=shape) == \
        blocks * cells
    assert reg.value("repro_chess_hvp_block_width", shape=shape) == 128
    assert reg.value("repro_chess_hvp_lanes_filled_pct", shape=shape) == \
        pytest.approx(100.0 * 300 / 384)


# ---------------------------------------------------------------------------
# shapes and modes the chip's compiler takes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,blk_m,want", [
    (500_000, 8, 128), (500_000, 128, 128), (500_000, 129, 256),
    (500_000, 1024, 1024), (5, 8, 5), (3, 16, 3), (300, 1, 128),
    (200, 129, 200),
])
def test_block_rows_heights_mosaic_takes(m, blk_m, want):
    """A block is a multiple of the 128-lane tile or all of m."""
    assert block_rows(m, blk_m) == want


def test_interpret_default_off_tpu_and_refused_on_it(monkeypatch):
    assert resolve_interpret(None) is True          # this CPU suite
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="refused on the TPU"):
        resolve_interpret(True)
