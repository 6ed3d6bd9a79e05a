"""Property-based kernel v2 coverage (runs only where hypothesis is
installed -- the dev extra): random (m, n, csize, blk_m, symmetric) combos
must agree with the vmap L2 reference, with ragged and padded shapes drawn
as first-class citizens, not special cases."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import testfns  # noqa: E402
from repro.kernels.chess_hvp import chess_hvp_pallas  # noqa: E402
from repro.kernels.ops import kernel_form  # noqa: E402
from repro.kernels.ref import chess_hvp_ref  # noqa: E402


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 300),                 # one or several lane blocks
    n=st.integers(2, 12),
    csize=st.integers(1, 14),
    blk_m=st.sampled_from([128, 256]),     # block widths Mosaic takes
    symmetric=st.booleans(),
    fname=st.sampled_from(["rosenbrock", "fletcher_powell"]),
    seed=st.integers(0, 2**16),
)
def test_chess_hvp_v2_property(m, n, csize, blk_m, symmetric, fname, seed):
    f = testfns.FUNCTIONS[fname](n)
    kf, consts = kernel_form(f)
    rng = np.random.RandomState(seed)
    A = jnp.asarray(rng.uniform(-2, 2, (m, n)), jnp.float32)
    V = jnp.asarray(rng.randn(m, n), jnp.float32)
    out = chess_hvp_pallas(kf, A, V, csize, consts=consts, blk_m=blk_m,
                           symmetric=symmetric)
    want = chess_hvp_ref(kf, A, V, csize, consts)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want),
        rtol=5e-3, atol=5e-3 * (1 + np.abs(np.asarray(want)).max()))
