"""Bring-up check: the main path of the library, run once on the TPU.

  python chip_smoke.py [--seed 0]      # one chip
  python chip_smoke.py --chips 4       # the mesh path on a 4-chip host

One chip runs two phases through the entry points a user calls:

* paper batch -- ``engine.plan(f, 16, m=500_000, csize="auto",
  backend="auto").batched_hvp(A, V)`` for Rosenbrock, Ackley and
  Fletcher-Powell (the paper's section 7 functions at its instance count),
  both schedules.  Each plan must resolve to the ``pallas`` kernel, whose
  compiled HLO must hold a ``tpu_custom_call``, and 1,024 sampled rows must
  match ``core.ref.hvp_fwdrev`` run on the host CPU.
* served path -- the curvature server's plans
  (``launch.serve.build_plans``) behind a ``CurvatureFrontend`` on an
  ephemeral port; 4 client connections send 256 HVP requests with n in
  {8, 12, 16, 64} at both priorities, and every answer is checked against
  the same CPU reference.  Both the cross-n ragged buckets and the per-n
  pallas buckets must run.

``--chips 4`` runs only the mesh path: ``sharded`` batched HVPs at
m=500,000 over a 4-way ("data",) mesh and ``sharded_rows`` HVPs and
Hessians at n=256 over a 4-way ("model",) mesh, each compared with the
same plan without a mesh (one chip) and with the reference, and each
output's sharding must span 4 devices.

The error limit is max |got - ref| <= 1e-3 * max |ref|.  Timings and
compile seconds are printed for information.  The last line of standard
output is ``{"ok": true, "device": {...}}``; any failure exits non-zero
before it, and so does a run where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# no stored autotune winner may steer the run: persistence off
os.environ["REPRO_AUTOTUNE_CACHE"] = ""
# the reference runs on the host CPU next to the chip
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import engine  # noqa: E402
from repro.compat import make_mesh  # noqa: E402
from repro.core import testfns  # noqa: E402
from repro.core.ref import hvp_fwdrev  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_plans  # noqa: E402
from repro.serving.frontend import CurvatureFrontend, connect  # noqa: E402

REL_TOL = 1e-3               # the bound launch/serve.py's selftest uses
FUNCTIONS = ("rosenbrock", "ackley", "fletcher_powell")
PAPER_N, PAPER_M, SAMPLED_ROWS = 16, 500_000, 1024
SERVED_NS, SERVED_REQUESTS, CLIENTS = (8, 12, 16, 64), 256, 4
ROWS_N = 256


def log(msg: str) -> None:
    print(msg, flush=True)


class Check:
    """Collects failures; a phase goes on after one so the log shows all."""

    def __init__(self):
        self.failures = []

    def expect(self, cond: bool, what: str) -> None:
        if not cond:
            self.failures.append(what)
            log(f"FAIL {what}")


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def reference_hvps(f, A, V):
    """hvp_fwdrev row by row on the host CPU."""
    cpu = jax.devices("cpu")[0]
    A, V = (jax.device_put(np.asarray(x), cpu) for x in (A, V))
    return np.asarray(jax.vmap(lambda a, v: hvp_fwdrev(f, a, v))(A, V))


def paper_inputs(seed: int, m: int, n: int):
    rng = np.random.default_rng(seed)
    A = rng.uniform(-2.0, 2.0, (m, n)).astype(np.float32)
    V = rng.standard_normal((m, n)).astype(np.float32)
    rows = np.sort(rng.choice(m, SAMPLED_ROWS, replace=False))
    return A, V, rows


def paper_batch(seed: int, check: Check) -> None:
    A, V, rows = paper_inputs(seed, PAPER_M, PAPER_N)
    A_dev, V_dev = jax.device_put(A), jax.device_put(V)
    for name in FUNCTIONS:
        f = testfns.FUNCTIONS[name](PAPER_N)
        want = reference_hvps(f, A[rows], V[rows])
        for symmetric in (False, True):
            tag = f"paper {name} symmetric={symmetric}"
            p = engine.plan(f, PAPER_N, m=PAPER_M, csize="auto",
                            backend="auto", symmetric=symmetric)
            backend = p.backend_for("batched_hvp")
            check.expect(backend == "pallas", f"{tag}: backend {backend}")
            t0 = time.perf_counter()
            hlo = p.executable("batched_hvp").lower(
                A_dev, V_dev).compile().as_text()
            t_compile = time.perf_counter() - t0
            custom = "tpu_custom_call" in hlo
            check.expect(custom, f"{tag}: no tpu_custom_call in the HLO")
            t0 = time.perf_counter()
            out = jax.block_until_ready(p.batched_hvp(A_dev, V_dev))
            t_first = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = jax.block_until_ready(p.batched_hvp(A_dev, V_dev))
            t_run = time.perf_counter() - t0
            got = np.asarray(out[rows])
            ok = out.shape == (PAPER_M, PAPER_N) and bool(
                np.isfinite(got).all())
            err = rel_err(got, want)
            check.expect(ok and err <= REL_TOL,
                         f"{tag}: shape {out.shape}, rel err {err:.3e}")
            log(f"{tag}: backend={backend} csize={p.csize} "
                f"tpu_custom_call={custom} compile_s={t_compile:.3f} "
                f"first_call_s={t_first:.3f} run_s={t_run:.3f} "
                f"points_per_s={PAPER_M / t_run:.0f} "
                f"sampled_rows={len(rows)} rel_err={err:.3e}")


def served_path(seed: int, check: Check) -> None:
    rng = np.random.default_rng(seed + 1)
    engine.clear_telemetry()
    fe = CurvatureFrontend(build_plans(FUNCTIONS), host="127.0.0.1", port=0)
    fe.start()
    host, port = fe.address
    clients = [connect(host, port, client=f"smoke-{i}")
               for i in range(CLIENTS)]
    try:
        reqs = []
        t0 = time.perf_counter()
        for i in range(SERVED_REQUESTS):
            name = FUNCTIONS[i % len(FUNCTIONS)]
            n = int(rng.choice(SERVED_NS))
            a = rng.uniform(-2.0, 2.0, n).astype(np.float32)
            v = rng.standard_normal(n).astype(np.float32)
            pr = "interactive" if i % 2 else "batch"
            fut = clients[i % CLIENTS].submit_hvp(name, a, v, priority=pr)
            reqs.append((name, n, a, v, fut))
        got = [np.asarray(fut.result(timeout=900), np.float32)
               for *_, fut in reqs]
        t_served = time.perf_counter() - t0
        stats = clients[0].stats()
    finally:
        for c in clients:
            c.close()
        fe.stop()

    worst, bad = 0.0, 0
    for (name, n, a, v, _fut), out in zip(reqs, got):
        f = testfns.FUNCTIONS[name](n)
        want = reference_hvps(f, a[None], v[None])[0]
        err = rel_err(out, want) if out.shape == (n,) else float("inf")
        worst = max(worst, err)
        bad += not err <= REL_TOL
    check.expect(bad == 0, f"served: {bad} of {len(reqs)} requests over "
                           f"the limit (worst rel err {worst:.3e})")
    executed = {}
    for rec in engine.execution_stats():
        key = (rec["backend"], rec["workload"])
        executed[key] = executed.get(key, 0) + sum(
            b["count"] for b in rec["by_bucket"].values())
    pallas = executed.get(("pallas", "batched_hvp"), 0)
    check.expect(stats.get("ragged_batches", 0) >= 1,
                 f"served: no ragged bucket ran ({stats})")
    check.expect(pallas >= 1, f"served: no per-n pallas bucket ran "
                              f"({executed})")
    log(f"served: {len(reqs)} requests from {CLIENTS} clients in "
        f"{t_served:.3f}s (compiles included); batches="
        f"{stats.get('batches')} ragged_batches="
        f"{stats.get('ragged_batches')} executed_buckets="
        + json.dumps({f"{b}/{w}": c for (b, w), c in sorted(
            executed.items())})
        + f" worst_rel_err={worst:.3e}")


def spans(out, count: int) -> bool:
    return len(out.sharding.device_set) == count


def four_chips(seed: int, check: Check) -> None:
    count = len(jax.devices())
    check.expect(count == 4, f"--chips 4 found {count} devices")
    if count != 4:
        return
    data = make_mesh((4,), ("data",))
    model = make_mesh((4,), ("model",))
    A, V, rows = paper_inputs(seed, PAPER_M, PAPER_N)
    for name in FUNCTIONS:
        tag = f"sharded {name} m={PAPER_M}"
        f = testfns.FUNCTIONS[name](PAPER_N)
        p4 = engine.plan(f, PAPER_N, m=PAPER_M, csize="auto", mesh=data)
        p1 = engine.plan(f, PAPER_N, m=PAPER_M, csize="auto")
        backend = p4.backend_for("batched_hvp")
        check.expect(backend == "sharded", f"{tag}: backend {backend}")
        t0 = time.perf_counter()
        out4 = jax.block_until_ready(p4.batched_hvp(A, V))
        t4 = time.perf_counter() - t0
        t0 = time.perf_counter()
        out1 = jax.block_until_ready(p1.batched_hvp(A, V))
        t1 = time.perf_counter() - t0
        check.expect(spans(out4, 4), f"{tag}: output on "
                     f"{len(out4.sharding.device_set)} devices")
        want = reference_hvps(f, A[rows], V[rows])
        e_ref = rel_err(np.asarray(out4[rows]), want)
        e_one = rel_err(np.asarray(out4), np.asarray(out1))
        check.expect(e_ref <= REL_TOL and e_one <= REL_TOL,
                     f"{tag}: rel err vs ref {e_ref:.3e}, vs one chip "
                     f"{e_one:.3e}")
        log(f"{tag}: backend={backend} csize={p4.csize} devices="
            f"{len(out4.sharding.device_set)} first_call_s 4 chips={t4:.3f} "
            f"1 chip ({p1.backend_for('batched_hvp')})={t1:.3f} "
            f"rel_err_ref={e_ref:.3e} rel_err_one_chip={e_one:.3e}")

    rng = np.random.default_rng(seed + 2)
    a = rng.uniform(-2.0, 2.0, ROWS_N).astype(np.float32)
    v = rng.standard_normal(ROWS_N).astype(np.float32)
    cpu = jax.devices("cpu")[0]
    for name in FUNCTIONS:
        f = testfns.FUNCTIONS[name](ROWS_N)
        p4 = engine.plan(f, ROWS_N, csize="auto", mesh=model)
        p1 = engine.plan(f, ROWS_N, csize="auto")
        h_ref = np.asarray(jax.jit(jax.hessian(f))(jax.device_put(a, cpu)))
        for wl, args, want in (("hvp", (a, v), reference_hvps(
                f, a[None], v[None])[0]), ("hessian", (a,), h_ref)):
            tag = f"sharded_rows {name} {wl} n={ROWS_N}"
            backend = p4.backend_for(wl)
            check.expect(backend == "sharded_rows",
                         f"{tag}: backend {backend}")
            out4 = jax.block_until_ready(getattr(p4, wl)(*args))
            out1 = jax.block_until_ready(getattr(p1, wl)(*args))
            check.expect(spans(out4, 4), f"{tag}: output on "
                         f"{len(out4.sharding.device_set)} devices")
            e_ref = rel_err(out4, want)
            e_one = rel_err(out4, out1)
            check.expect(e_ref <= REL_TOL and e_one <= REL_TOL,
                         f"{tag}: rel err vs ref {e_ref:.3e}, vs one chip "
                         f"{e_one:.3e}")
            log(f"{tag}: backend={backend} csize={p4.csize} devices="
                f"{len(out4.sharding.device_set)} rel_err_ref={e_ref:.3e} "
                f"rel_err_one_chip={e_one:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path, on a 4-chip host")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU, JAX found {device}", file=sys.stderr)
        return 2
    log(f"device: {json.dumps(device)}")

    cache_dir = enable_compile_cache()
    events: dict = {}
    jax.monitoring.register_event_listener(
        lambda event, **_: events.__setitem__(event, events.get(event, 0)
                                              + 1))

    check = Check()
    phases = ([("four_chips", four_chips)] if args.chips == 4 else
              [("paper_batch", paper_batch), ("served_path", served_path)])
    for label, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed, check)
        except Exception as e:        # a phase that raised has failed
            traceback.print_exc()
            check.expect(False, f"{label} raised {type(e).__name__}: {e}")
        log(f"phase {label}: {time.perf_counter() - t0:.3f}s")
    log(f"compile cache {cache_dir}: hits="
        f"{events.get('/jax/compilation_cache/cache_hits', 0)} misses="
        f"{events.get('/jax/compilation_cache/cache_misses', 0)}")
    if check.failures:
        print(f"chip_smoke: {len(check.failures)} failures", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
