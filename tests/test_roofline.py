"""launch.roofline --curvature: sweep-count gate and accounting helpers.

The gate is the CI tripwire that symmetric schedules never regress from
skipping (compacted grids / cyclic cell lists) back to masking: a
symmetric row executing more chunk cells than the triangle bound must
fail.  The measured wall-clock rows are exercised by the bench-smoke CI
step, not here -- these tests cover the static accounting, which is what
the gate trusts.
"""

import pytest

from repro.core.api import num_chunk_evals
from repro.launch.hlo_analysis import peaks_for
from repro.launch.roofline import (_executed_cells, _sweep_gate,
                                   render_curvature)


def _rec(backend, sched, executed, minimum, **kw):
    r = {"backend": backend, "schedule": sched, "n": 8, "csize": 4,
         "cells_executed": executed, "cells_min": minimum}
    r.update(kw)
    return r


def test_sweep_gate_passes_exact_triangle():
    recs = [_rec("pallas", "sym", 12, 12),
            _rec("pallas", "full", 16, 16),
            _rec("vmap_l2", "sym", 12, 12)]
    assert _sweep_gate(recs) == []


def test_sweep_gate_catches_masked_ghosts():
    """A v2-style schedule (full grid launched, triangle masked) must trip
    the gate."""
    recs = [_rec("pallas", "sym", 16, 12)]
    fails = _sweep_gate(recs)
    assert fails and "pallas" in fails[0]


def test_sweep_gate_sharded_padding_slack():
    """The cyclic sharded layout pads every shard to the max kept count:
    executed may exceed the triangle by the declared allowance, but KEPT
    must equal the triangle exactly."""
    ok = _rec("sharded_rows", "sym", 96, 84, cells_allowed=156,
              cells_kept=84)
    assert _sweep_gate([ok]) == []
    bad_kept = _rec("sharded_rows", "sym", 96, 84, cells_allowed=156,
                    cells_kept=90)
    assert _sweep_gate([bad_kept])
    over = _rec("sharded_rows", "sym", 200, 84, cells_allowed=156,
                cells_kept=84)
    assert _sweep_gate([over])


@pytest.mark.parametrize("n,csize,sym", [(12, 4, True), (12, 4, False),
                                         (13, 4, True), (8, 8, True)])
def test_executed_cells_match_schedule_enumeration(n, csize, sym):
    """The roofline report's cell accounting equals the schedules' own
    static enumeration on every backend column."""
    want = num_chunk_evals(n, csize, sym)
    assert _executed_cells("vmap_l2", 8, n, csize, 8, sym) == want
    assert _executed_cells("pallas", 8, n, csize, 8, sym) == want


def test_cyclic_sharded_accounting_consistent():
    """The static sharded_rows row the report emits: kept == triangle and
    executed within the one-block-per-shard padding slack."""
    from repro.core.distributed import cyclic_layout

    n, csize, size = 48, 4, 4
    lay = cyclic_layout(n, csize, size)
    tri = num_chunk_evals(n, csize, True)
    assert sum(lay.kept) == tri
    executed = size * lay.executed
    assert tri <= executed <= tri + (size - 1) * lay.block_cells_bound


def test_render_curvature_table_md():
    recs = [_rec("vmap_l2", "full", 16, 16, flops=1e6, bytes=1e5,
                 measured_s=2e-4, bound_s=1e-6, pct_roofline=0.5),
            _rec("vmap_l2", "sym", 12, 12, flops=6e5, bytes=6e4,
                 measured_s=1e-4, bound_s=6e-7, pct_roofline=0.6)]
    txt = render_curvature(recs, md=True)
    assert txt.startswith("| backend")
    assert "speedup = 2.00x" in txt


def test_peaks_table_is_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.flops, p.hbm_bw) == (197e12, 819e9) and "v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")


def test_render_says_not_measured_off_the_chip():
    rec = _rec("vmap_l2", "full", 16, 16, flops=1e6, bytes=1e5,
               measured_s=2e-4, bound_s=None, pct_roofline=None,
               status="measured")
    assert "not measured" in render_curvature([rec])
