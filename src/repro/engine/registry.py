"""Backend registry for the CurvatureEngine.

A *backend* is a named strategy for executing one or more curvature
workloads.  Registering a backend is a one-file change: provide a factory
``make(plan, workload) -> callable`` plus a capability declaration, and the
planner's ``backend="auto"`` selection and the executable cache pick it up.

Workloads (positional array signatures of the produced callable):

  "hvp"             (a, v)   -> r          single instance, flat vectors
  "hessian"         (a,)     -> H          dense Hessian, flat vector
  "batched_hvp"     (A, V)   -> R          m instances, (m, n) arrays
  "batched_hessian" (A,)     -> Hs         (m, n) -> (m, n, n)
  "diag"            (params, key) -> tree  Hutchinson diag(H) on pytrees
                                           (diag_of="ggn" estimates diag(G))
  "quadform"        (params, v, w) -> scalar  w^T H v, pure-forward
  "ggn"             (params, v) -> tree    Gauss-Newton (J^T H_head J) v;
                                           needs model_fn/head_loss options
  "fisher"          (params, v) -> tree    empirical Fisher (1/B) J_L^T J_L v;
                                           needs the per_example_fn option
  "batched_diag"    (A, K) -> (m, size)    coalesced pytree diag: raveled
                                           param rows + PRNG-key rows
  "batched_hvp_ragged" (A, V, NE) -> R     mixed-n HVP rows padded to one
                                           (m, n_pad) bucket; NE carries
                                           each row's effective dimension
                                           (needs the ragged_family option;
                                           see docs/serving.md)

Flat backends (``flat_only=True``) require ``plan.n`` to be a concrete int;
pytree backends accept arbitrary parameter trees and are selected when
``plan.n is None``.  A pytree plan whose options carry a ``pytree_spec``
(engine/pytree.py) additionally serves the batched workloads on RAVELED
(m, size) rows -- that is how the CurvatureService coalesces pytree
requests through the same micro-bucket path as flat plans.
"""

from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from repro import obs

__all__ = [
    "BackendSpec", "register_backend", "get_backend", "list_backends",
    "resolve_backend", "WORKLOADS",
    "record_execution", "execution_stats", "clear_telemetry",
    "DTYPE_POLICIES", "policy_compute_dtype", "bucket_telemetry",
    "client_stats",
]

WORKLOADS = ("hvp", "hessian", "batched_hvp", "batched_hessian", "diag",
             "quadform", "ggn", "fisher", "batched_diag",
             "batched_hvp_ragged")

# dual-number dtype policies (the HomebrewNLP-style host/dtype dial made a
# plan option): "fp32" runs the hDual sweeps in the input dtype (default),
# "bf16" casts the seed point so every tangent component is bfloat16 while
# accumulation stays fp32, "fp64" widens (requires jax x64).  A backend
# advertises which policies its schedules actually honor; plans carrying a
# non-default ``dtype_policy`` option only resolve to capable backends.
DTYPE_POLICIES = ("fp32", "bf16", "fp64")


def policy_compute_dtype(policy: str):
    """The compute dtype a policy casts tangent sweeps to (None = keep the
    input dtype, i.e. the "fp32" default on fp32 inputs)."""
    if policy in (None, "fp32"):
        return None
    import jax.numpy as jnp
    if policy == "bf16":
        return jnp.bfloat16
    if policy == "fp64":
        return jnp.float64
    raise ValueError(
        f"unknown dtype_policy {policy!r}; expected one of {DTYPE_POLICIES}")


@dataclass(frozen=True)
class BackendSpec:
    """One executable strategy in the registry.

    make(plan, workload) returns the raw (unjitted) callable for the
    workload; the planner wraps it with the trace-counting jit and caches
    the result.  ``supports`` may veto a (plan, workload) combination that
    the static declaration alone cannot rule out (e.g. csize divisibility).
    """
    name: str
    make: Callable
    workloads: frozenset
    priority: int = 0
    requires_mesh: bool = False
    flat_only: bool = True
    supports: Optional[Callable] = None
    doc: str = ""
    # dual dtype policies the backend's schedules honor; the default keeps
    # every backend on the exact path unless it opts in (see DTYPE_POLICIES)
    dtype_policies: frozenset = frozenset({"fp32"})

    def can_run(self, plan, workload: str) -> bool:
        if workload not in self.workloads:
            return False
        if self.requires_mesh and plan.mesh is None:
            return False
        if self.flat_only and plan.n is None:
            return False
        if plan.opt("dtype_policy", "fp32") not in self.dtype_policies:
            return False
        if self.supports is not None and not self.supports(plan, workload):
            return False
        return True


_REGISTRY: dict[str, BackendSpec] = {}
_ENSURED = False


def register_backend(spec: BackendSpec) -> BackendSpec:
    """Idempotent by name: re-registration replaces (supports reload)."""
    _REGISTRY[spec.name] = spec
    return spec


def _ensure_builtin_backends() -> None:
    """Import the modules that self-register backends.

    Lazy so that `import repro.core` never pulls in the engine, while any
    engine entry point sees the full registry.  Each import is tolerant of
    missing optional deps (e.g. Pallas off-platform)."""
    global _ENSURED
    if _ENSURED:
        return
    # mandatory backends first; _ENSURED is only set once they are all in,
    # so a failing import is retried (and its root cause re-raised) on the
    # next engine call instead of leaving a half-populated registry
    import repro.engine.backends  # noqa: F401  (reference / vmap / sharded)
    import repro.core.curvature  # noqa: F401  (pytree backends)
    try:
        import repro.kernels.ops  # noqa: F401  (pallas, optional layer)
    except Exception as e:  # pragma: no cover - pallas unavailable
        # on TPU this is the production path: a missing kernel layer is an
        # error there, and only a warning where pallas is a parity path
        import jax
        if jax.default_backend() == "tpu":
            raise
        import warnings
        warnings.warn(f"pallas backend unavailable "
                      f"(repro.kernels.ops failed to import): {e!r}")
    _ENSURED = True


def get_backend(name: str) -> BackendSpec:
    _ensure_builtin_backends()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def list_backends() -> dict[str, BackendSpec]:
    _ensure_builtin_backends()
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# execution telemetry
# ---------------------------------------------------------------------------
#
# Every executed bucket can be reported here: (plan signature, backend,
# workload) -> measured us/point samples, tagged with the padded bucket size.
# The CurvatureService records each dispatch; anything else (benchmarks,
# autotune) may too.  Since PR 3 this history is LIVE: ``backend="auto"``
# resolution consults it (after the joint autotuner's persisted winners)
# before falling back to static priorities -- see ``_learned_backend``.

_TELEMETRY_MAXSAMPLES = 256          # ring buffer per (signature, bucket)
_TELEMETRY: collections.OrderedDict = collections.OrderedDict()
_TELEMETRY_MAXKEYS = 512             # keys strong-reference f: LRU-bound
_TELEMETRY_VERSION = 0               # bumps on mutation (consult memo)
_TELEMETRY_LOCK = threading.Lock()
# decay/expiry of the consult-path best (PR 4): one transient fast (or
# slow) measurement must not pin backend="auto" forever, so the best a
# signature advertises is the minimum over its most recent
# _TELEMETRY_WINDOW samples, each inflated by 2**(age / halflife) --
# sample-count rollover AND wall-clock age both un-pin a stale winner.
_TELEMETRY_WINDOW = 64               # samples the consult best considers
_TELEMETRY_HALFLIFE_S = 600.0        # age doubling period for old samples
_TELEMETRY_DRIFT = 1.05              # upward best drift tolerated silently
_BUCKET_RECENT = 32                  # timestamped window per (sig, bucket)


# per-client serving totals (PR 9): the dispatcher tags every executed
# bucket with the clients whose rows it carried, so operators can read who
# the service is actually working for (points = real rows served, batches =
# buckets the client had at least one row in).  Aggregated service-wide --
# the per-signature tags live on the telemetry entries ("by_client").
_CLIENT_TOTALS: dict = {}


def clear_telemetry() -> None:
    global _TELEMETRY_VERSION
    with _TELEMETRY_LOCK:
        _TELEMETRY.clear()
        _CLIENT_TOTALS.clear()
        _TELEMETRY_VERSION += 1


class _ExecMetrics:
    """Cached children for the execution emit (once per executed BUCKET,
    not per request; docs/observability.md).  Only the us/point
    distribution and the execution count are written here -- they have no
    other home, since the bespoke telemetry keeps windowed samples, not
    histograms.  Per-client totals are served by the scrape-time
    ``_collect_clients`` collector over ``client_stats()`` instead."""

    __slots__ = ("_exec", "_us", "_by_bw")

    def __init__(self):
        reg = obs.default_registry()
        self._exec = reg.counter(
            "repro_executions_total", "Executed buckets by executable.",
            labelnames=("backend", "workload"))
        self._us = reg.histogram(
            "repro_execution_us_per_point",
            "Measured microseconds per real point per executed bucket.",
            labelnames=("backend", "workload"))
        self._by_bw = {}

    def children(self, backend: str, workload: str):
        key = (backend, workload)
        ent = self._by_bw.get(key)
        if ent is None:
            ent = self._by_bw[key] = (
                self._exec.child(backend=backend, workload=workload),
                self._us.child(backend=backend, workload=workload))
        return ent


_EXEC_MX = None


def _exec_mx() -> _ExecMetrics:
    global _EXEC_MX
    if _EXEC_MX is None:
        _EXEC_MX = _ExecMetrics()
    return _EXEC_MX


def _flush_exec_mx() -> None:
    global _EXEC_MX
    _EXEC_MX = None


obs.on_reset(_flush_exec_mx)


def _collect_clients(reg) -> None:
    """Scrape-time collector: per-client serving totals as views over the
    ``client_stats()`` telemetry the dispatcher already maintains."""
    if not obs.enabled():
        return
    totals = client_stats()
    if not totals:
        return
    pts = reg.counter("repro_client_points_total",
                      "Rows executed on behalf of each client.",
                      labelnames=("client",))
    bat = reg.counter("repro_client_batches_total",
                      "Buckets that carried at least one row of each "
                      "client.", labelnames=("client",))
    for cid, tot in totals.items():
        pts.child(client=cid).set(tot["points"])
        bat.child(client=cid).set(tot["batches"])


obs.default_registry().set_collector("engine.clients", _collect_clients)


def record_execution(signature, backend: str, workload: str, *,
                     bucket: int, n_points: int, elapsed_s: float,
                     now: Optional[float] = None,
                     clients: Optional[dict] = None) -> None:
    """Record one executed bucket: ``n_points`` real points served by an
    executable padded to ``bucket`` rows in ``elapsed_s`` seconds.

    ``signature`` is the plan's executable cache key (hashable); us/point is
    charged to the REAL points, so padding waste shows up as a higher
    us/point at ragged sizes.  Thread-safe: the service dispatcher calls
    this from its own thread.

    The consult-path best this feeds is NOT monotonic (PR 4): it is the
    minimum over the entry's most recent ``_TELEMETRY_WINDOW`` samples,
    each inflated by ``2 ** (age / _TELEMETRY_HALFLIFE_S)``.  A transient
    outlier therefore un-pins once the observation window rolls past it
    (or it ages out), instead of steering ``backend="auto"`` forever.
    ``now`` injects a clock for deterministic tests.

    ``clients`` optionally tags the bucket with ``{client_id: row_count}``
    (the serving dispatcher passes the per-client row mix): tags
    accumulate on the signature entry (``by_client``) and service-wide
    (``client_stats()``)."""
    global _TELEMETRY_VERSION
    if n_points <= 0:
        return
    t = time.monotonic() if now is None else float(now)
    us_per_point = elapsed_s / n_points * 1e6
    with _TELEMETRY_LOCK:
        entry = _TELEMETRY.get(signature)
        if entry is None:
            entry = {"backend": backend, "workload": workload,
                     "best_us": float("inf"), "by_bucket": {},
                     "recent": collections.deque(maxlen=_TELEMETRY_WINDOW)}
            _TELEMETRY[signature] = entry
            while len(_TELEMETRY) > _TELEMETRY_MAXKEYS:
                _TELEMETRY.popitem(last=False)
        else:
            _TELEMETRY.move_to_end(signature)
        samples = entry["by_bucket"].setdefault(
            int(bucket), collections.deque(maxlen=_TELEMETRY_MAXSAMPLES))
        samples.append(float(us_per_point))
        # timestamped short window per bucket: what the online re-tuner's
        # drift detector reads (recent mean vs the tuned baseline)
        recent_b = entry.setdefault("by_bucket_recent", {}).setdefault(
            int(bucket), collections.deque(maxlen=_BUCKET_RECENT))
        recent_b.append((float(us_per_point), t))
        if clients:
            by_client = entry.setdefault("by_client", collections.Counter())
            for cid, rows in clients.items():
                by_client[cid] += int(rows)
                tot = _CLIENT_TOTALS.setdefault(
                    cid, {"points": 0, "batches": 0})
                tot["points"] += int(rows)
                tot["batches"] += 1
        entry["recent"].append((float(us_per_point), t))
        best = min(us * 2.0 ** (max(0.0, t - ts) / _TELEMETRY_HALFLIFE_S)
                   for us, ts in entry["recent"])
        # bump the consult version on improvement or MATERIAL upward drift
        # (window/age rollover), but swallow the continuous age creep a
        # pinned old sample produces: bumping on every float change would
        # invalidate the _LEARNED_CACHE memo each bucket and put a full
        # telemetry scan back on the serving hot path (a 5% stale best
        # cannot flip a steering decision that the next 5% step won't)
        if best < entry["best_us"] or best > entry["best_us"] * _TELEMETRY_DRIFT:
            entry["best_us"] = float(best)
            _TELEMETRY_VERSION += 1
    # emit the distribution OUTSIDE the telemetry lock; once per bucket,
    # so this does not scale with request rate.  The bespoke dicts above
    # stay the source of truth for the consult path and the stats()
    # views; counters derivable from them are fed by scrape-time
    # collectors instead (parity witnessed in tests/test_obs.py)
    if obs.enabled():
        exec_c, us_c = _exec_mx().children(backend, workload)
        exec_c.inc()
        us_c.observe(us_per_point)


def execution_stats() -> list[dict]:
    """Summarize recorded executions: one dict per plan signature with
    per-bucket (count, mean/min us/point).  Plain data, safe to json-dump
    after stringifying keys."""
    out = []
    with _TELEMETRY_LOCK:
        items = [(k, {"backend": v["backend"], "workload": v["workload"],
                      "by_bucket": {b: list(s)
                                    for b, s in v["by_bucket"].items()}})
                 for k, v in _TELEMETRY.items()]
    for sig, entry in items:
        buckets = {}
        for b, samples in sorted(entry["by_bucket"].items()):
            buckets[b] = {
                "count": len(samples),
                "us_per_point_mean": sum(samples) / len(samples),
                "us_per_point_min": min(samples),
            }
        out.append({"signature": sig, "backend": entry["backend"],
                    "workload": entry["workload"], "by_bucket": buckets})
    return out


def client_stats() -> dict:
    """Service-wide per-client serving totals: ``{client_id: {"points",
    "batches"}}`` accumulated from every ``record_execution`` call that
    carried client tags (the serving dispatcher tags each bucket with the
    clients whose rows it coalesced).  Cleared by ``clear_telemetry``."""
    with _TELEMETRY_LOCK:
        return {cid: dict(tot) for cid, tot in _CLIENT_TOTALS.items()}


def bucket_telemetry(signature) -> dict:
    """Per-bucket recent telemetry for one plan signature: ``{bucket:
    {"count", "recent_us_mean", "recent_us_min", "last_t"}}`` over the
    timestamped short window (``_BUCKET_RECENT`` newest samples).  This is
    the live objective the online re-tuner compares against its learned
    winner -- ``count`` is the total samples ever recorded for the bucket,
    the ``recent_*`` fields summarize only the window."""
    with _TELEMETRY_LOCK:
        entry = _TELEMETRY.get(signature)
        if entry is None:
            return {}
        out = {}
        for b, samples in entry["by_bucket"].items():
            recent = list(entry.get("by_bucket_recent", {}).get(b, ()))
            info = {"count": len(samples)}
            if recent:
                us = [u for u, _t in recent]
                info.update(recent_us_mean=sum(us) / len(us),
                            recent_us_min=min(us),
                            last_t=recent[-1][1])
            out[int(b)] = info
        return out


def _telemetry_best(plan, workload: str, names: dict, fp: str):
    """The capable backend with the best recorded windowed us/point for
    this exact (f, n, csize, symmetric, mesh, workload) signature, or None.

    Signatures are the plan cache keys the service reports; the function
    slot is matched by identity first, fingerprint second, so history
    recorded by another plan object for the same function still counts.
    Decisions use the per-signature windowed+age-decayed best (see
    ``record_execution``), so a stale outlier eventually un-pins.
    History is MESH-KEYED: a signature only matches a plan with the same
    mesh (None matches None), so single-device telemetry can never promote
    a sharded pick for a mesh plan nor vice versa.
    Negative-priority backends (correctness-only paths -- interpret-mode
    pallas off-TPU) never steal auto resolution here, however good their
    recorded numbers look."""
    from .autotune import function_fingerprint
    with _TELEMETRY_LOCK:
        items = [(k, v["backend"], v["workload"],
                  v.get("best_us", float("inf")))
                 for k, v in _TELEMETRY.items()]
    best_name, best_us = None, float("inf")
    for sig, backend, wl, us in items:
        spec = names.get(backend)
        if (wl != workload or spec is None or spec.priority < 0
                or not us < float("inf")):
            continue
        try:
            sf, sn, sc, ssym, _sbk, smesh = sig[:6]
        except (TypeError, ValueError):
            continue
        if (sn != plan.n or sc != plan.csize
                or bool(ssym) != plan.symmetric or smesh != plan.mesh):
            continue
        if sf is not plan.f:
            try:
                if function_fingerprint(sf) != fp:
                    continue
            except Exception:   # pragma: no cover
                continue
        if us < best_us:
            best_name, best_us = backend, us
    return best_name


# memoized consult decisions: the learned pick for a plan signature only
# changes when the tuner's consult table or the telemetry table mutate, so
# resolve_backend (called on EVERY plan execution) pays two dict lookups on
# the steady-state path instead of a telemetry scan
_LEARNED_CACHE: collections.OrderedDict = collections.OrderedDict()
_LEARNED_CACHE_MAXSIZE = 512


def _learned_backend(plan, workload: str, candidates):
    """PR 3: what ``backend="auto"`` learned about this plan -- the joint
    autotuner's persisted winner first (exact csize match so a tuned
    record never steers a differently-chunked plan), then execution
    telemetry -- before static priorities get a say.

    Mesh plans consult too (PR 4), but the whole pipeline is mesh-keyed:
    the tuner never records mesh winners (``lookup_tuned`` is None there),
    telemetry only matches same-mesh signatures, and the memo key carries
    the mesh -- so learned history can never leak across topologies."""
    if plan.n is None:
        return None
    names = {s.name: s for s in candidates}
    # NB name-level imports: the package re-exports the autotune FUNCTION
    # under the submodule's name, so `from . import autotune` would bind
    # the function, not the module
    try:
        from .autotune import (function_fingerprint, lookup_tuned,
                               tuned_version)
        fp = function_fingerprint(plan.f)
    except Exception:       # pragma: no cover - consult must never break
        return None
    key = (fp, plan.n, plan.csize, plan.symmetric, plan.m, workload,
           plan.mesh)
    versions = (tuned_version(), _TELEMETRY_VERSION)
    with _TELEMETRY_LOCK:
        hit = _LEARNED_CACHE.get(key)
        if hit is not None and hit[0] == versions:
            _LEARNED_CACHE.move_to_end(key)
            return names.get(hit[1])

    name = None
    try:
        cfg = lookup_tuned(plan, workload)
    except Exception:       # pragma: no cover
        cfg = None
    if (cfg is not None and cfg.backend in names
            and cfg.csize == plan.csize):
        name = cfg.backend
    else:
        name = _telemetry_best(plan, workload, names, fp)
    with _TELEMETRY_LOCK:
        _LEARNED_CACHE[key] = (versions, name)
        while len(_LEARNED_CACHE) > _LEARNED_CACHE_MAXSIZE:
            _LEARNED_CACHE.popitem(last=False)
    return names.get(name)


def resolve_backend(plan, workload: str) -> BackendSpec:
    """Pick the backend for a (plan, workload) pair.

    Explicit names are honored (error if incapable).  "auto" resolution is
    topology-aware FIRST (PR 4): a mesh-carrying plan asked for
    distribution, so when any mesh-native backend (``requires_mesh``) is
    capable of the workload on this mesh, the candidate set narrows to
    those before anything else gets a say -- ``batched_hvp`` resolves to
    ``sharded``, ``hvp``/``hessian`` to ``sharded_rows`` on a model-axis
    mesh; workloads with no mesh-native backend (or meshes lacking the
    needed axis) fall through to the single-device backends.  Within the
    candidate set, learned history is consulted (the joint autotuner's
    persisted winner for flat plans, then mesh-keyed execution telemetry)
    and only then static priorities decide."""
    _ensure_builtin_backends()
    if plan.backend != "auto":
        spec = get_backend(plan.backend)
        if not spec.can_run(plan, workload):
            raise ValueError(
                f"backend {spec.name!r} cannot run workload {workload!r} "
                f"for plan {plan.describe()}")
        return spec
    candidates = [s for s in _REGISTRY.values() if s.can_run(plan, workload)]
    if not candidates:
        raise ValueError(
            f"no registered backend supports workload {workload!r} for "
            f"plan {plan.describe()}; registered: {sorted(_REGISTRY)}")
    if plan.mesh is not None:
        mesh_native = [s for s in candidates if s.requires_mesh]
        if mesh_native:
            candidates = mesh_native
    learned = _learned_backend(plan, workload, candidates)
    if learned is not None:
        return learned
    return max(candidates, key=lambda s: (s.priority, s.name))
