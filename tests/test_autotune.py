"""Joint autotuner acceptance: function fingerprinting shared by both
caches, best-of-k timing under a deadline, the (csize, backend, blk_m)
sweep, disk persistence (including the cross-process zero-probe claim, CI
checked via subprocesses), and the backend="auto" history consult."""

import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import ref, testfns
# NB: repro.engine re-exports the autotune FUNCTION under the submodule's
# name, so the module itself must come from sys.modules
import repro.engine.autotune  # noqa: F401
at = sys.modules["repro.engine.autotune"]

N, M = 8, 8


@pytest.fixture(autouse=True)
def _clean_state():
    """Each test starts with no in-memory tuner/telemetry state (the
    session-scoped disk store from conftest is left alone unless a test
    points REPRO_AUTOTUNE_CACHE elsewhere)."""
    engine.clear_autotune_cache()
    engine.clear_telemetry()
    yield
    engine.clear_autotune_cache()
    engine.clear_telemetry()


# ---------------------------------------------------------------------------
# function_fingerprint: one identity for both caches
# ---------------------------------------------------------------------------

def test_fingerprint_stable_and_content_sensitive():
    fp1 = engine.function_fingerprint(testfns.rosenbrock)
    assert fp1 == engine.function_fingerprint(testfns.rosenbrock)
    assert fp1.startswith("rosenbrock:")
    # distinct functions -> distinct fingerprints
    assert fp1 != engine.function_fingerprint(testfns.ackley)


def test_fingerprint_hashes_closure_contents():
    def make(c):
        def f(x):
            return ((x * c) * x).sum(0)
        return f

    # same source, different closure constant -> different identity
    assert (engine.function_fingerprint(make(2.0))
            != engine.function_fingerprint(make(3.0)))
    # same source, same closure constant, DIFFERENT objects -> same identity
    # (this is what the old strong-reference key got wrong: identity was
    # per-object, so equal closures re-tuned and pinned forever)
    assert (engine.function_fingerprint(make(2.0))
            == engine.function_fingerprint(make(2.0)))


def test_fingerprint_hashes_coefficient_arrays():
    # fletcher_powell closes over numpy coefficient arrays: content-hashed
    f8a = testfns.make_fletcher_powell(8)
    f8b = testfns.make_fletcher_powell(8, seed=1964)
    f16 = testfns.make_fletcher_powell(16)
    fps = {engine.function_fingerprint(g) for g in (f8a, f8b, f16)}
    assert len(fps) == 3


# ---------------------------------------------------------------------------
# _time_once: best-of-k under a deadline budget
# ---------------------------------------------------------------------------

def test_time_once_best_of_k_and_deadline():
    calls = []

    def fn():
        calls.append(time.perf_counter())
        time.sleep(0.02)
        return np.float32(0.0)

    t = at._time_once(fn, reps=3, deadline_s=None)
    assert len(calls) == 4              # 1 warmup + 3 timed
    assert 0.015 <= t <= 0.2            # best-of-3 of a ~20ms fn

    calls.clear()
    before = engine.probe_count()
    at._time_once(fn, reps=50, deadline_s=0.05)
    # deadline cuts the rep loop long before 50: 1 warmup + a few reps
    assert 2 <= len(calls) <= 10
    assert engine.probe_count() == before + len(calls)


# ---------------------------------------------------------------------------
# the joint sweep
# ---------------------------------------------------------------------------

def test_joint_autotune_returns_measured_config(monkeypatch, tmp_path):
    # fresh store: other test FILES (test_engine's autotune smoke) may have
    # persisted this exact signature to the session store, which would turn
    # the asserted fresh sweep into a disk restore under non-alphabetical
    # test ordering (pre-existing order dependence, fixed in PR 4)
    monkeypatch.setenv(at.STORE_ENV, str(tmp_path / "autotune.json"))
    engine.clear_autotune_cache()
    cfg = engine.autotune(testfns.rosenbrock, N, m=M, reps=1,
                          symmetric=False)
    assert isinstance(cfg, engine.TunedConfig)
    assert cfg.csize in engine.csize_candidates(N)
    assert cfg.backend in engine.list_backends()
    assert cfg.time_s > 0.0 and cfg.source == "sweep"
    # memo hit: same object back, no new probes
    probes = engine.probe_count()
    assert engine.autotune(testfns.rosenbrock, N, m=M, reps=1,
                           symmetric=False) is cfg
    assert engine.probe_count() == probes


def test_pruned_candidates_seed_the_grid():
    pruned = engine.pruned_csize_candidates(64, symmetric=True)
    full = engine.csize_candidates(64)
    assert set(pruned) <= set(full)
    assert engine.model_csize(64, True) in pruned


def test_autotuned_plan_consults_history_for_backend():
    cfg = engine.autotune(testfns.rosenbrock, N, m=M, reps=1,
                          symmetric=False)
    p = engine.plan(testfns.rosenbrock, N, m=M, csize="autotune",
                    symmetric=False)
    assert p.csize == cfg.csize
    # backend="auto" resolves to the tuner's winner, not static priority
    assert p.backend_for("batched_hvp") == cfg.backend
    # a plan at a DIFFERENT csize must not be steered by the record
    other = next(c for c in engine.csize_candidates(N) if c != cfg.csize)
    p2 = engine.plan(testfns.rosenbrock, N, m=M, csize=other,
                     symmetric=False)
    assert p2.backend_for("batched_hvp") == "vmap_l2"   # static CPU pick


def test_auto_backend_consults_telemetry(monkeypatch):
    # persistence off: a session-store record for this signature would
    # (correctly) outrank telemetry and break the static-pick baseline
    monkeypatch.setenv(at.STORE_ENV, "")
    engine.clear_autotune_cache()
    f = testfns.ackley
    p = engine.plan(f, N, m=M, csize=2, symmetric=False)
    assert p.backend_for("batched_hvp") == "vmap_l2"
    # live traffic measured vmap_l1 faster for this exact signature
    sig = p.cache_key("batched_hvp", "vmap_l1")
    engine.record_execution(sig, "vmap_l1", "batched_hvp", bucket=8,
                            n_points=8, elapsed_s=1e-5)
    assert p.backend_for("batched_hvp") == "vmap_l1"
    # the learned pick executes correctly
    rng = np.random.RandomState(3)
    A = jnp.asarray(rng.uniform(-2, 2, (M, N)), jnp.float32)
    V = jnp.asarray(rng.randn(M, N), jnp.float32)
    out = p.batched_hvp(A, V)
    want = jnp.stack([ref.hvp_fwdrev(f, A[i], V[i]) for i in range(M)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    engine.clear_telemetry()
    assert p.backend_for("batched_hvp") == "vmap_l2"


def test_telemetry_never_promotes_negative_priority_backends(monkeypatch):
    """A recorded sample from a correctness-only path (interpret-mode
    pallas on CPU has priority -5) must not steal auto resolution."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("pallas has positive priority on TPU")
    monkeypatch.setenv(at.STORE_ENV, "")    # see telemetry test above
    engine.clear_autotune_cache()
    f = testfns.rosenbrock
    p = engine.plan(f, N, m=M, csize=2, symmetric=False, interpret=True)
    sig = p.cache_key("batched_hvp", "pallas")
    engine.record_execution(sig, "pallas", "batched_hvp", bucket=8,
                            n_points=8, elapsed_s=1e-9)   # "fastest ever"
    assert p.backend_for("batched_hvp") == "vmap_l2"


def test_mesh_tune_does_not_clobber_flat_consult(monkeypatch):
    """A mesh-plan autotune (csize-only, backend resolved per-plan) shares
    the flat store key; it must not overwrite the flat joint winner."""
    import jax
    from repro.compat import make_mesh
    monkeypatch.setenv(at.STORE_ENV, "")    # in-memory consult only
    engine.clear_autotune_cache()
    f = testfns.rosenbrock
    cfg = engine.autotune(f, N, m=M, reps=1, symmetric=False)
    mesh = make_mesh((len(jax.devices()),), ("data",))
    engine.autotune(f, N, m=M, reps=1, symmetric=False, mesh=mesh)
    p = engine.plan(f, N, m=M, csize=cfg.csize, symmetric=False)
    assert p.backend_for("batched_hvp") == cfg.backend


def test_candidates_include_ragged_csizes():
    """Kernel v2 lifted csize | n, so the tuner grid must too: at n=12 the
    old divisor cap was 4; 8 and the over-wide 16 are now candidates."""
    assert engine.csize_candidates(12) == [1, 2, 4, 8, 16]
    assert engine.csize_candidates(8) == [1, 2, 4, 8]      # pow2 unchanged
    assert engine.csize_candidates(1) == [1]
    assert max(engine.csize_candidates(1000)) == engine.LANE_WIDTH


def test_pallas_blk_m_threads_into_plan(monkeypatch):
    """An explicit-backend pallas tune sweeps the lane-wide block widths
    narrower than its probe batch against the kernel's derived width
    (None), and a winning width lands in the plan's options."""
    opts = (("interpret", True),)
    # a probe batch of 8: no swept width is narrower, so only the
    # derived width (one block of the batch) runs and nothing is pinned
    cfg = engine.autotune(testfns.rosenbrock, 4, m=8, reps=1,
                          symmetric=False, backend="pallas", options=opts)
    assert cfg.backend == "pallas" and cfg.blk_m is None
    p = engine.plan(testfns.rosenbrock, 4, m=8, csize="autotune",
                    backend="pallas", symmetric=False, interpret=True)
    assert p.csize == cfg.csize
    assert p.opt("blk_m") is None
    # a probe batch of 300: 256 lanes against the derived width (128)
    cfg = engine.autotune(testfns.rosenbrock, 4, m=300, probe_m=75, reps=1,
                          symmetric=False, backend="pallas", options=opts)
    assert cfg.backend == "pallas" and cfg.blk_m in (256, None)
    # a tuned width is threaded into the plan
    won = at.TunedConfig(csize=2, backend="pallas", blk_m=256, time_s=0.0,
                         source="sweep")
    monkeypatch.setattr(at, "autotune", lambda *a, **k: won)
    p = engine.plan(testfns.rosenbrock, 4, m=4096, csize="autotune",
                    backend="pallas", symmetric=False, interpret=True)
    assert p.csize == 2 and p.opt("blk_m") == 256


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def test_store_round_trip_in_process(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(at.STORE_ENV, path)
    engine.clear_autotune_cache()       # forget the session store snapshot

    cfg = engine.autotune(testfns.rosenbrock, N, m=M, reps=1,
                          symmetric=False)
    data = json.load(open(path))
    assert len(data) == 1
    (key, entry), = data.items()
    assert key.startswith("rosenbrock:")
    assert entry["csize"] == cfg.csize and entry["backend"] == cfg.backend
    assert entry["time_s"] > 0

    # wipe in-memory state: the disk record alone must answer, zero probes
    engine.clear_autotune_cache()
    probes = engine.probe_count()
    cfg2 = engine.autotune(testfns.rosenbrock, N, m=M, reps=1,
                           symmetric=False)
    assert engine.probe_count() == probes
    assert (cfg2.csize, cfg2.backend, cfg2.source) == (
        cfg.csize, cfg.backend, "disk")
    # and the consult table serves resolve_backend from the same record
    p = engine.plan(testfns.rosenbrock, N, m=M, csize="autotune",
                    symmetric=False)
    assert engine.probe_count() == probes
    assert p.backend_for("batched_hvp") == cfg.backend


def test_corrupt_store_is_ignored(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    with open(path, "w") as fh:
        fh.write("{ not json")
    monkeypatch.setenv(at.STORE_ENV, path)
    engine.clear_autotune_cache()
    cfg = engine.autotune(testfns.rosenbrock, N, m=M, reps=1,
                          symmetric=False)
    assert cfg.source == "sweep"        # fell through to the microbenchmark
    assert json.load(open(path))        # and repaired the store on save


def test_persistence_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv(at.STORE_ENV, "")
    engine.clear_autotune_cache()
    cfg = engine.autotune(testfns.ackley, N, m=M, reps=1, symmetric=False)
    assert cfg.source == "sweep"
    assert not os.path.exists(os.path.join(str(tmp_path), "autotune.json"))


def test_disabled_store_api_noops(tmp_path, monkeypatch):
    """The sentinel values disable the public store API too -- save_store
    must not create a file literally named '0'."""
    monkeypatch.setenv(at.STORE_ENV, "0")
    monkeypatch.chdir(tmp_path)
    engine.clear_autotune_cache()
    assert engine.load_store() == {}
    assert engine.save_store() is None
    assert not os.path.exists(str(tmp_path / "0"))
    # sentinels never become the path even for direct callers
    assert at.store_path().endswith("autotune.json")


def test_store_platform_includes_device_kind():
    plat = at._platform()
    assert ":" in plat           # backend:device_kind, not just "cpu"/"tpu"


def test_include_pallas_is_part_of_the_memo_key(monkeypatch):
    """An explicit include_pallas=True sweep must not be answered by a
    cached default sweep that never probed pallas."""
    monkeypatch.setenv(at.STORE_ENV, "")
    engine.clear_autotune_cache()
    cfg_default = engine.autotune(testfns.rosenbrock, 4, m=8, reps=1,
                                  symmetric=False,
                                  options=(("interpret", True),))
    cfg_pallas = engine.autotune(testfns.rosenbrock, 4, m=8, reps=1,
                                 symmetric=False, include_pallas=True,
                                 options=(("interpret", True),))
    assert cfg_pallas is not cfg_default      # distinct memo entries


def test_store_survives_process_restart(tmp_path):
    """Acceptance: a FRESH process with a warm store plans csize="autotune"
    without running a single timed probe."""
    path = str(tmp_path / "autotune.json")
    env = dict(os.environ, REPRO_AUTOTUNE_CACHE=path)
    # repro is a namespace package (__file__ is None): derive src/ from a
    # real module three levels down
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(testfns.__file__))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")

    script1 = (
        "from repro import engine\n"
        "from repro.core import testfns\n"
        "cfg = engine.autotune(testfns.rosenbrock, 4, m=8, reps=1,\n"
        "                      symmetric=False)\n"
        "print('TUNE', cfg.csize, cfg.backend, engine.probe_count())\n")
    out1 = subprocess.run([sys.executable, "-c", script1], env=env,
                          capture_output=True, text=True, timeout=600)
    assert out1.returncode == 0, out1.stderr
    tag, csize1, backend1, probes1 = out1.stdout.split()[-4:]
    assert tag == "TUNE" and int(probes1) > 0
    assert os.path.exists(path)

    script2 = (
        "from repro import engine\n"
        "from repro.core import testfns\n"
        "p = engine.plan(testfns.rosenbrock, 4, m=8, csize='autotune',\n"
        "                symmetric=False)\n"
        "assert engine.probe_count() == 0, engine.probe_count()\n"
        "print('PLAN', p.csize, p.backend_for('batched_hvp'),\n"
        "      engine.probe_count())\n")
    out2 = subprocess.run([sys.executable, "-c", script2], env=env,
                          capture_output=True, text=True, timeout=600)
    assert out2.returncode == 0, out2.stderr
    tag, csize2, backend2, probes2 = out2.stdout.split()[-4:]
    assert tag == "PLAN"
    assert int(probes2) == 0            # the microbenchmark was skipped
    assert csize2 == csize1             # and the same winner was restored
    assert backend2 == backend1
