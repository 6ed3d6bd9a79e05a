"""chess_hvp compiled with Mosaic for a described TPU v5e, at the paper's
width: n=16, m=500,000 instances, csize=4 for the three section-7
functions and both schedules, plus one ragged chunking (csize=5).

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would.  The
topology is described inside a fixture, so only the test process that is
given this file loads the TPU library; every test here compiles in that
process.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import testfns
from repro.kernels.chess_hvp import chess_hvp_pallas
from repro.kernels.ops import kernel_form

N, M = 16, 500_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off here
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_hlo(one_chip, fname: str, csize: int, symmetric: bool) -> str:
    kf, consts = kernel_form(testfns.FUNCTIONS[fname](N))

    def run(A, V, *cs):
        return chess_hvp_pallas(kf, A, V, csize, consts=cs,
                                symmetric=symmetric, interpret=False)

    x = jax.ShapeDtypeStruct((M, N), jnp.float32, sharding=one_chip)
    cs = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
          for c in consts]
    return jax.jit(run).lower(x, x, *cs).compile().as_text()


@pytest.mark.parametrize("symmetric", [False, True])
@pytest.mark.parametrize("fname", ["rosenbrock", "ackley",
                                   "fletcher_powell"])
def test_chess_hvp_compiles_for_v5e(one_chip, fname, symmetric):
    assert "tpu_custom_call" in _compiled_hlo(one_chip, fname, 4, symmetric)


def test_chess_hvp_ragged_chunk_compiles_for_v5e(one_chip):
    assert "tpu_custom_call" in _compiled_hlo(one_chip, "fletcher_powell", 5,
                                              True)
