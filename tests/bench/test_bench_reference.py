"""The benchmark's own reference against the library, at small m on the
CPU, for both configurations."""

import jax
import numpy as np
import pytest

from bench_helpers import run
from bench import reference
from repro.core import testfns
from repro.core.ref import hvp_fwdrev


def _inputs(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.uniform(-2, 2, (m, n)).astype(np.float32),
            rng.standard_normal((m, n)).astype(np.float32))


@pytest.mark.parametrize("n", [3, 8, 16, 64])
def test_fletcher_powell_coefficients_are_the_libraries(n):
    cfg = run.load_config(run.load_benchmark(), "fletcher_powell")
    for mine, lib in zip(cfg.module.coefficients(n), testfns._fp_coeffs(n)):
        np.testing.assert_array_equal(mine, lib)


@pytest.mark.parametrize("config", ["rosenbrock", "fletcher_powell"])
@pytest.mark.parametrize("n", [8, 16])
def test_reference_agrees_with_the_library(config, n):
    cfg = run.load_config(run.load_benchmark(), config)
    A, V = _inputs(64, n)
    ref = reference.hvp_float64(cfg.module.formula(n), A, V)
    assert ref.dtype == np.float64 and ref.shape == (64, n)
    f = cfg.module.objective(n)
    lib = np.asarray(jax.vmap(lambda a, v: hvp_fwdrev(f, a, v))(A, V))
    assert reference.row_rel_err(lib, ref).max() < 1e-5


def test_reference_is_float64_and_leaves_x64_off():
    cfg = run.load_config(run.load_benchmark(), "rosenbrock")
    A, V = _inputs(4, 8)
    reference.hvp_float64(cfg.module.formula(8), A, V)
    assert jax.numpy.ones(1).dtype == np.float32


def test_control_in_bfloat16_is_far_from_float64():
    cfg = run.load_config(run.load_benchmark(), "rosenbrock")
    A, V = _inputs(256, 16)
    f = cfg.module.formula(16)
    ref = reference.hvp_float64(f, A, V)
    low = reference.hvp_in(f, A, V, "bfloat16", jax.devices("cpu")[0])
    assert low.dtype == np.float32
    assert reference.row_rel_err(low, ref).max() > 1e-3


def test_row_rel_err():
    want = np.array([[1.0, -4.0], [2.0, 0.0]])
    np.testing.assert_array_equal(reference.row_rel_err(want, want), [0, 0])
    got = np.array([[1.0, -3.0], [np.nan, 0.0]])
    np.testing.assert_allclose(reference.row_rel_err(got, want),
                               [0.25, np.inf])
    assert np.all(np.isinf(reference.row_rel_err(want[:, :1], want)))
