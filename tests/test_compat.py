"""repro.compat.make_mesh: Auto axes unless the caller names others, and
meshes that ``jax.shard_map`` runs on."""

import jax
import numpy as np
from jax.sharding import AxisType

from repro import compat


def test_make_mesh_axes_are_auto():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_make_mesh_keeps_explicit_axis_types():
    mesh = compat.make_mesh((1,), ("data",),
                            axis_types=(AxisType.Explicit,))
    assert mesh.axis_types == (AxisType.Explicit,)


def test_shim_executes_on_installed_jax():
    """jax.shard_map over a compat mesh, used as a decorator factory."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("data",))

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def double(x):
        return x * 2.0

    out = double(jax.numpy.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(4))
