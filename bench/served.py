"""The served driver: the curvature server as ``launch/serve.py`` deploys it,
driven over TCP from the client side.

The configuration's ``serve_plans()`` gives the plan registry of
``launch.serve.build_plans``; it goes behind a ``CurvatureFrontend`` on an
ephemeral local port, with a ``CurvatureService`` at the server's CLI
defaults (``SERVER_DEFAULTS``).  The request recorder is
replaced by one that holds every trace of a run, so a per-request tail can
be read from its spans.

Traffic parameters (``bench/traffic/<name>.json``, ``"driver": "served"``):

* ``arrivals`` -- ``"closed"``: ``callers`` callers, each with one request
  outstanding, sending its next on reply; ``"poisson"``: requests due at
  ``rate_rps`` (a fixed count per window, see ``arrivals.py``), sent
  whether or not earlier ones were answered;
* ``connections`` -- TCP connections the requests are spread over;
* ``n_mix`` -- ``{n: weight}``: the request pool holds these widths in
  exactly these shares, in an order drawn from the seed;
* ``pool`` -- distinct requests made from the seed, used in turn;
* ``priorities`` -- cycled over callers (closed) or requests (poisson);
* ``a_low``, ``a_high`` -- a ~ U(a_low, a_high); v ~ N(0, 1);
* ``warm_seconds`` -- the traffic itself, run and discarded before the
  window, after every bucket shape the mix can form has been compiled.

The CPU tests may add ``server``, knobs that override ``SERVER_DEFAULTS``,
to run at sizes a test can hold; no traffic file of a cell sets it.

Every reply of the window is compared with the float64 reference;
``control_reading`` gives the control of that comparison on the same pool.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from . import reference
from .arrivals import OpenLoop, poisson_arrivals
from .common import Checked, host_rng

__all__ = ["Driver", "control_reading", "SMALL"]

DRAIN_S = 60.0          # how long a reply may come after the window closes
TRACE_CAPACITY = 1_000_000
# the defaults of ``python -m repro.launch.serve`` (its --max-batch,
# --max-wait-us, --max-queue, --coalesce-waste-max; cross-n coalescing on,
# no admission controller)
SERVER_DEFAULTS = {"max_batch": 64, "max_wait_us": 500.0, "max_queue": 4096,
                   "coalesce_across_n": True, "coalesce_waste_max": 0.4}
# the overrides that run a cell of this driver at a size a CPU test can hold
SMALL = {"config": {},
         "traffic": {"n_mix": {"8": 0.5, "12": 0.5}, "pool": 128,
                     "warm_seconds": 0.2, "callers": 8, "rate_rps": 200.0,
                     "server": {"max_batch": 4, "max_wait_us": 500.0}}}


def _pool(rng, n_mix: dict, size: int, a_low: float, a_high: float):
    """``size`` requests whose widths hold exactly the shares of ``n_mix``."""
    ns, weights = zip(*sorted((int(n), float(w)) for n, w in n_mix.items()))
    counts = np.floor(np.asarray(weights) / sum(weights) * size).astype(int)
    counts[np.argmax(counts)] += size - counts.sum()
    widths = np.repeat(ns, counts)
    rng.shuffle(widths)
    reqs = [(rng.uniform(a_low, a_high, n).astype(np.float32),
             rng.standard_normal(n).astype(np.float32)) for n in widths]
    return widths, reqs


def _pow2_upto(top: int):
    b = 1
    while b <= top:
        yield b
        b *= 2


def warm_buckets(factory, ns, max_batch: int) -> int:
    """Compile every bucket the mix can form: per-n buckets for each width,
    and ragged buckets at each width a mixed bucket can pad to.  Runs on
    the device the dispatch worker pins.  Returns the programs run."""
    from repro import engine
    ns = sorted(ns)
    ran = 0
    rng = np.random.default_rng(0)
    with jax.default_device(jax.local_devices()[0]):
        for n in ns:
            p = factory(n)
            for b in _pow2_upto(max_batch):
                A = rng.standard_normal((b, n)).astype(np.float32)
                jax.block_until_ready(p.executable("batched_hvp")(A, A))
                ran += 1
            fam = p.opt("ragged_family")
        if fam is None:
            return ran
        for n_pad in ns[1:]:
            p = engine.plan(fam, n_pad, symmetric=False)
            for b in _pow2_upto(max_batch):
                if b < 2:
                    continue        # a mixed bucket holds two rows or more
                A = rng.standard_normal((b, n_pad)).astype(np.float32)
                NE = np.full(b, n_pad, np.int32)
                jax.block_until_ready(
                    p.executable("batched_hvp_ragged")(A, A, NE))
                ran += 1
    return ran


class _Log:
    """One row per request sent: pool index, due time, reply future; and
    the reply times, by row."""

    def __init__(self):
        self.lock = threading.Lock()
        self.idx, self.due, self.futures = [], [], []
        self.done = {}

    def add(self, idx, due, fut) -> int:
        with self.lock:
            i = len(self.idx)
            self.idx.append(idx)
            self.due.append(due)
            self.futures.append(fut)
        return i


class Driver:
    def __init__(self, config, traffic: dict, seed: int):
        from repro import engine, obs
        from repro.obs import trace as obs_trace
        from repro.serving.frontend import CurvatureFrontend, connect
        self.config, self.traffic = config, traffic
        self.name = config.spec["function"]
        self.recorder = obs.FlightRecorder(capacity=TRACE_CAPACITY)
        self._prior_recorder = obs.recorder()
        obs_trace._replace_default(self.recorder)
        knobs = {**SERVER_DEFAULTS, **traffic.get("server", {})}
        self.service = engine.CurvatureService(**knobs)
        self.plans = config.module.serve_plans()
        self.frontend = CurvatureFrontend(self.plans, service=self.service,
                                          host="127.0.0.1", port=0).start()
        host, port = self.frontend.address
        self.clients = [connect(host, port)
                        for _ in range(int(traffic["connections"]))]
        self.widths, self.pool = _pool(
            host_rng(seed, 1), traffic["n_mix"], int(traffic["pool"]),
            float(traffic["a_low"]), float(traffic["a_high"]))
        self.priorities = list(traffic["priorities"])
        self.warm_programs = warm_buckets(
            self.plans[self.name], [int(n) for n in traffic["n_mix"]],
            int(knobs["max_batch"]))
        self.arrival_rng = host_rng(seed, 2)
        self.log = None
        self._next = 0
        self._drive(float(traffic["warm_seconds"]))     # discarded

    # -- sending ------------------------------------------------------------

    def _send(self, j: int, k: int, priority: str):
        """Send request j (pool entry j mod pool) as sender k, on
        connection k mod connections."""
        a, v = self.pool[j % len(self.pool)]
        conn = self.clients[k % len(self.clients)]
        return conn.submit_hvp(self.name, a, v, client=f"sender-{k}",
                               priority=priority)

    def _drive(self, seconds: float) -> _Log:
        kind = self.traffic["arrivals"]
        if kind == "closed":
            return self._closed(seconds)
        if kind == "poisson":
            return self._poisson(seconds)
        raise ValueError(f"unknown arrivals {kind!r}")

    def _closed(self, seconds: float) -> _Log:
        log = _Log()
        callers = int(self.traffic["callers"])
        t0 = time.perf_counter()
        t_end = t0 + seconds
        counter = iter(range(self._next, 1 << 62))
        lock = threading.Lock()

        def launch(c: int) -> None:
            with lock:
                j = next(counter)
            now = time.perf_counter() - t0
            fut = self._send(j, c, self.priorities[c % len(self.priorities)])
            i = log.add(j % len(self.pool), now, fut)
            fut.add_done_callback(lambda f, i=i, c=c: on_done(i, c))

        def on_done(i: int, c: int) -> None:
            now = time.perf_counter()
            log.done[i] = now - t0
            if now < t_end:
                launch(c)

        with jax.profiler.TraceAnnotation("bench:window"):
            for c in range(callers):
                launch(c)
            time.sleep(max(t_end - time.perf_counter(), 0.0))
        self._next = next(counter)
        self._drain(log)
        self._drain(log)        # what callbacks sent as the window closed
        log.t0, log.seconds = t0, seconds
        return log

    def _poisson(self, seconds: float) -> _Log:
        due = poisson_arrivals(self.arrival_rng,
                               float(self.traffic["rate_rps"]), seconds)
        start = self._next
        self._next += len(due)
        loop = OpenLoop(due)

        def send(i: int):
            j = start + i
            return self._send(j, j % len(self.clients),
                              self.priorities[j % len(self.priorities)])

        with jax.profiler.TraceAnnotation("bench:window"):
            loop.run(send)
            time.sleep(max(loop.t0 + seconds - time.perf_counter(), 0.0))
        log = _Log()
        for i, fut in enumerate(loop.futures):
            log.add((start + i) % len(self.pool), loop.due[i], fut)
        log.t0, log.seconds, log.loop = loop.t0, seconds, loop
        self._drain(log)
        log.done = {i: t for i, t in enumerate(loop.done) if t == t}
        return log

    @staticmethod
    def _drain(log: _Log) -> None:
        deadline = time.perf_counter() + DRAIN_S
        for fut in list(log.futures):
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            try:
                fut.exception(timeout=left)
            except TimeoutError:
                break

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> float:
        before = self.service.stats()
        self.log = self._drive(seconds)
        after = self.service.stats()
        self.stats_delta = {k: after[k] - before[k]
                            for k in ("dispatched", "batches",
                                      "ragged_batches", "padded_rows")}
        return seconds

    def end_to_end(self) -> dict:
        log = self.log
        done = np.asarray([log.done.get(i, np.inf)
                           for i in range(len(log.futures))])
        out = {"served_rps": float(np.sum(done <= log.seconds))
               / log.seconds}
        if self.traffic["arrivals"] == "poisson":
            sojourn = done - np.asarray(log.due)
            out["sojourn_p95_ms"] = float(np.percentile(sojourn, 95)) * 1e3
        return out

    def counters(self) -> dict:
        log = self.log
        t_lo, t_hi = log.t0, log.t0 + log.seconds
        waits = []
        for tr in self.recorder.recent(TRACE_CAPACITY):
            if not t_lo <= tr.t_start <= t_hi:
                continue
            waits.append(sum(t1 - t0 for name, t0, t1, _m in tr.spans
                             if name in ("enqueue", "coalesce",
                                         "dispatch_wait")))
        out = {"requests": len(log.futures), **self.stats_delta,
               "warm_programs": self.warm_programs, "queue_wait_s": waits}
        if self.traffic["arrivals"] == "poisson":
            late = log.loop.lateness_s()
            out["generator_late_p99_ms"] = float(np.percentile(late, 99)) * 1e3
            out["generator_late_max_ms"] = float(np.max(late)) * 1e3
        return out

    def release(self) -> None:
        """Stop the server and the clients; keep the replies."""
        from repro.obs import trace as obs_trace
        for c in self.clients:
            c.close()
        self.frontend.stop()
        self.service.shutdown(wait=True)
        obs_trace._replace_default(self._prior_recorder)

    def check(self) -> Checked:
        limit = float(self.config.spec["max_rel_err"])
        log = self.log
        res = Checked(attempted=len(log.futures))
        got = {}
        for i, fut in enumerate(log.futures):
            if not fut.done() or fut.exception() is not None:
                res.failed += 1
                continue
            got[i] = np.asarray(fut.result(), np.float32)
        worst = 0.0
        for n in sorted(set(int(self.widths[k]) for k in set(log.idx))):
            rows = [i for i in got if self.widths[log.idx[i]] == n]
            keys = sorted(set(log.idx[i] for i in rows))
            if not keys:
                continue
            A = np.stack([self.pool[k][0] for k in keys])
            V = np.stack([self.pool[k][1] for k in keys])
            ref = dict(zip(keys, reference.hvp_float64(
                self.config.module.formula(n), A, V)))
            for i in rows:
                err = float(reference.row_rel_err(
                    got[i][None], ref[log.idx[i]][None])[0])
                worst = max(worst, err)
                res.failed += not err <= limit
        res.add("max_request_rel_err", worst, limit)
        return res


def control_reading(config, traffic: dict, seed: int, dtype: str) -> float:
    """The control on the cell's request pool: the reference computed in
    ``dtype`` in the program's place; its widest per-row gap."""
    widths, pool = _pool(
        host_rng(seed, 1), traffic["n_mix"], int(traffic["pool"]),
        float(traffic["a_low"]), float(traffic["a_high"]))
    worst = 0.0
    for n in sorted(set(int(w) for w in widths)):
        rows = [p for p, w in zip(pool, widths) if w == n]
        A = np.stack([a for a, _ in rows])
        V = np.stack([v for _, v in rows])
        worst = max(worst, reference.widest_gap(config.module.formula(n),
                                                A, V, dtype))
    return worst
