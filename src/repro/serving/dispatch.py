"""Dispatch layer: execute coalesced batches on devices, resolve futures.

The serving stack (docs/serving.md) is transport -> admission ->
scheduler -> **dispatch**.  This module turns the scheduler's ready
batches into device work:

  * **worker threads, one per device** -- each worker parks on the
    scheduler's ``wake`` event / deadline timer, pops ready batches and
    executes them inside a ``jax.default_device`` context for its pinned
    device.  On a single-device host this degenerates to exactly the old
    one-dispatcher-thread service; with k devices, k plan queues drain
    concurrently.  All workers share the plan executable cache and every
    queue's hot-swapped ``exec_by_bucket`` winners, so the PR-8 re-tune
    contract (swaps never drop in-flight work) is unchanged.
  * **dense buckets** -- single-n batches stack to (k, n), pad to the
    power-of-two bucket (``pad_rows`` edge replication) and run the
    queue's ordinary ``batched_hvp`` / ``batched_hessian`` /
    ``batched_diag`` executable, honoring any re-tuned per-bucket winner.
  * **ragged buckets** -- a batch holding MORE THAN ONE row width (the
    scheduler's cross-n fill) pads every row to ``n_pad = max(n)``
    (``pad_cols``), stacks the effective widths into an ``NE`` vector and
    runs the RaggedGroup's ``batched_hvp_ragged`` executable; each future
    resolves to its own first ``n`` entries.  Telemetry for these batches
    is recorded under the group plan's signature, and they are excluded
    from the per-queue re-tune epoch (the tuner reasons about the dense
    executables only).
  * **telemetry** -- every executed bucket reports measured us/point to
    ``registry.record_execution``, now with per-client row counts so
    ``registry.client_stats`` can witness which clients shared a batch.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.engine import registry
from repro.engine.plan import bucket_size, pad_cols, pad_rows

from .scheduler import PlanQueue, Scheduler

__all__ = ["Dispatcher"]


def _record_batch_spans(live, marks, meta: dict) -> None:
    """Attach the scheduling/execution spans to every traced request of a
    batch.  ``meta`` is ONE shared dict per batch (bucket id, pad stats,
    cross-n family) referenced by all member spans -- the flight recorder
    never mutates it.

    Coalescing (``take_ready_batch`` entry -> selection, stamped once per
    batch on every member), the dispatch wait, marshalling, device
    execution and readback are batch-level intervals, so those five spans
    are built ONCE as a shared tuple-of-tuples and extended onto each
    member's span list; only the enqueue span differs per request (its own
    submit time).  ``marks`` are the bucket's stage boundaries from
    ``Dispatcher._run``: marshal start, execute start, execute end (after
    ``block_until_ready``) and readback end."""
    t_m, t0, t1, t2 = marks
    shared = None
    for r in live:
        tr = r.trace
        if tr is None:
            continue
        sel = tr.marks.get("selected", t_m)
        c0 = tr.marks.get("coalesce", sel)
        if shared is None:
            shared = (("coalesce", c0, sel, meta),
                      ("dispatch_wait", sel, t_m, None),
                      ("marshal", t_m, t0, None),
                      ("device_execute", t0, t1, meta),
                      ("readback", t1, t2, None))
        tr.add_span("enqueue", tr.marks.get("enqueued", tr.t_start), c0)
        tr.spans.extend(shared)


def _fail_traces(live, exc: Exception) -> None:
    for r in live:
        if r.trace is not None:
            r.trace.finish(error=type(exc).__name__)


_NO_SPAN = contextlib.nullcontext()


def _stage(active: bool, name: str, **meta):
    """The ``repro:<stage>`` profiler annotation while a capture runs
    (``active`` is read once per bucket), else a shared no-op context:
    outside a capture no annotation object is constructed."""
    return obs.annotate(name, **meta) if active else _NO_SPAN


class Dispatcher:
    """Executes batches popped from a Scheduler and runs the worker pool."""

    def __init__(self, sched: Scheduler, *, workers: Optional[int] = None):
        """``workers=None`` sizes the pool to the local device count (the
        single-device default is one worker, the old dispatcher thread).
        ``workers=0`` is the inline mode (``start=False`` services): no
        threads, batches execute on whoever calls ``run_once``."""
        self.sched = sched
        self.devices = list(jax.local_devices())
        if workers is None:
            workers = len(self.devices)
        if workers < 0:
            raise ValueError(f"workers={workers} must be >= 0")
        self.n_workers = int(workers)
        self.threads: list = []

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        for i in range(self.n_workers):
            dev = self.devices[i % len(self.devices)] if self.devices else None
            t = threading.Thread(
                target=self._worker_loop, args=(dev,),
                name=f"curvature-dispatch-{i}", daemon=True)
            t.start()
            self.threads.append(t)

    def join(self) -> None:
        ts, self.threads = self.threads, []
        for t in ts:
            t.join()

    # -- draining -----------------------------------------------------------

    def run_once(self, now=None, force: bool = False) -> int:
        """Pop-and-execute until no queue is ready; returns requests run."""
        sched = self.sched
        if now is None and not force:
            now = sched.clock()
        dispatched = 0
        while True:
            batch = sched.take_ready_batch(now, force=force)
            if batch is None:
                return dispatched
            q, reqs = batch
            self.execute(q, reqs)
            dispatched += len(reqs)

    def _run_pinned(self, dev, force: bool = False) -> int:
        # jax.default_device returns a single-use context manager; enter a
        # fresh one per pass so the worker's device pin survives the loop
        if dev is None:
            return self.run_once(force=force)
        with jax.default_device(dev):
            return self.run_once(force=force)

    def _worker_loop(self, dev) -> None:
        sched = self.sched
        while True:
            sched.wake.clear()
            if sched.closed:
                # drain: no submits can arrive anymore.  Every worker
                # drains (take_ready_batch pops atomically, so batches are
                # never executed twice) and re-raises the wake so sibling
                # workers parked in an unbounded wait also exit.
                self._run_pinned(dev, force=True)
                sched.wake.set()
                return
            if self._run_pinned(dev) > 0:
                continue
            with sched.lock:
                if sched.closed:
                    continue        # loop back to the drain branch
                delay = sched.next_deadline_delay()
            # wait for a submit nudge or the oldest request's deadline
            if obs.is_active():
                with obs.annotate("repro:worker_wait"):
                    sched.wake.wait(delay)
            else:
                sched.wake.wait(delay)

    # -- execution ----------------------------------------------------------

    def execute(self, q: PlanQueue, reqs) -> None:
        """Run one coalesced bucket and resolve its futures."""
        live = [r for r in reqs if r.future.set_running_or_notify_cancel()]
        if len(live) != len(reqs):
            alive = set(map(id, live))
            for r in reqs:
                if id(r) not in alive and r.trace is not None:
                    r.trace.finish(error="cancelled")
        if not live:
            return
        if q.group is not None and len({r.n for r in live}) > 1:
            self._execute_ragged(q, live)
            return
        sched = self.sched
        k = len(live)
        bucket = bucket_size(k, sched.max_batch)
        # per-bucket hot-swap: the re-tune loop installs winner executables
        # keyed by bucket; requests queued before a swap still execute (on
        # the new winner) and their futures resolve -- nothing is dropped.
        with sched.lock:
            tuned = q.exec_by_bucket.get(bucket)
        xplan, xbackend, xkey = tuned if tuned is not None \
            else (q.plan, q.backend, q.key)

        def marshal():
            # BOTH operands are marshalled before the execute stage:
            # telemetry must charge the same work to hvp and hessian
            # buckets (execution + readback, not host-to-device
            # marshalling).  Pytree buckets were raveled per request at
            # submit time, so this is still ONE device transfer per
            # operand per bucket.
            A = jnp.asarray(pad_rows(np.stack([r.a for r in live]), bucket))
            if q.workload == "batched_hessian":
                return (A,)
            V = jnp.asarray(pad_rows(np.stack([r.v for r in live]), bucket))
            if q.workload == "batched_diag":
                # per-row probe budgets: padding rows inherit the last
                # row's budget (their output is sliced off anyway)
                return A, V, jnp.asarray(pad_rows(
                    np.asarray([r.p for r in live], np.int32), bucket))
            return A, V                # pytree + flat hvp alike

        meta = {"bucket": bucket, "rows": k, "backend": xbackend,
                "workload": q.workload, "n_pad": int(live[0].a.shape[0])}
        ran = self._run(live, xplan, q.workload, marshal, meta)
        if ran is None:
            return
        out, marks, active = ran
        # telemetry charges the executable that actually ran -- after a
        # hot-swap the winner's signature accumulates the fresh history the
        # drift detector compares against its tuned baseline
        registry.record_execution(xkey, xbackend, q.workload,
                                  bucket=bucket, n_points=k,
                                  elapsed_s=marks[3] - marks[1],
                                  clients=self._client_rows(live))
        with sched.lock:
            sched.stats["dispatched"] += k
            sched.stats["batches"] += 1
            sched.stats["padded_rows"] += bucket - k
            sched.stats["buckets"][bucket] += 1
            q.epoch_counts[bucket] += k
            q.epoch_points += k
        traced = obs.enabled()
        if traced:
            _record_batch_spans(live, marks, {
                **meta, "padded_rows": bucket - k, "ragged": False})
        with _stage(active, "repro:respond"):
            for i, r in enumerate(live):
                tr = r.trace if traced else None
                r0 = tr.clock() if tr is not None else 0.0
                # copy: out[i] would be a view pinning the whole padded
                # bucket (max_batch rows) for as long as the client keeps
                # its result
                row = out[i].copy()
                if q.spec is not None:
                    try:
                        row = q.spec.unravel(row)
                    except Exception as e:  # pragma: no cover - spec bug
                        r.future.set_exception(e)
                        if tr is not None:
                            tr.finish(error=type(e).__name__)
                        continue
                r.future.set_result(row)
                if tr is not None:
                    # "respond" covers unravel + future resolution, which
                    # runs the frontend's done-callback (socket write)
                    # synchronously
                    tr.add_span("respond", r0, tr.clock())
                    tr.finish()

    def _execute_ragged(self, q: PlanQueue, live) -> None:
        """Run one mixed-n bucket through the family's ragged executable."""
        sched = self.sched
        k = len(live)
        bucket = bucket_size(k, sched.max_batch)
        n_pad = max(r.n for r in live)
        with sched.lock:
            gplan, gbackend, gkey = q.group.plan_for(n_pad)

        def marshal():
            A = jnp.asarray(pad_rows(np.stack(
                [pad_cols(np.asarray(r.a), n_pad) for r in live]), bucket))
            V = jnp.asarray(pad_rows(np.stack(
                [pad_cols(np.asarray(r.v), n_pad) for r in live]), bucket))
            NE = jnp.asarray(pad_rows(
                np.asarray([r.n for r in live], np.int32), bucket))
            return A, V, NE

        meta = {"bucket": bucket, "rows": k, "backend": gbackend,
                "workload": "batched_hvp_ragged", "n_pad": n_pad}
        ran = self._run(live, gplan, "batched_hvp_ragged", marshal, meta)
        if ran is None:
            return
        out, marks, active = ran
        registry.record_execution(gkey, gbackend, "batched_hvp_ragged",
                                  bucket=bucket, n_points=k,
                                  elapsed_s=marks[3] - marks[1],
                                  clients=self._client_rows(live))
        with sched.lock:
            sched.stats["dispatched"] += k
            sched.stats["batches"] += 1
            sched.stats["padded_rows"] += bucket - k
            sched.stats["buckets"][bucket] += 1
            sched.stats["ragged_batches"] += 1
            sched.stats["ragged_points"] += k
            # NOT counted into q.epoch_counts: the re-tune loop reasons
            # about the queue's dense executables, and ragged batches run
            # the group plan instead
        traced = obs.enabled()
        if traced:
            ns = [r.n for r in live]
            _record_batch_spans(live, marks, {
                **meta, "padded_rows": bucket - k, "ragged": True,
                "family": q.group.family.name,
                "pad_waste": round(
                    1.0 - sum(ns) / float(len(ns) * n_pad), 4)})
        with _stage(active, "repro:respond"):
            for i, r in enumerate(live):
                tr = r.trace if traced else None
                r0 = tr.clock() if tr is not None else 0.0
                r.future.set_result(out[i, :r.n].copy())
                if tr is not None:
                    tr.add_span("respond", r0, tr.clock())
                    tr.finish()

    @staticmethod
    def _run(live, plan, workload: str, marshal, meta: dict):
        """Marshal, execute and read back one bucket, each stage under its
        ``repro:`` annotation while a capture runs.  Returns ``(out, marks,
        active)``: the host output, the stage boundaries (marshal start,
        execute start, execute end after ``block_until_ready``, readback
        end) and whether a capture was running; or None after failing
        every future of the bucket."""
        active = obs.is_active()
        try:
            t_m = time.perf_counter()
            with _stage(active, "repro:marshal"):
                xargs = marshal()
                exe = plan.executable(workload)
            t0 = time.perf_counter()
            with _stage(active, "repro:device_execute", **meta):
                out = jax.block_until_ready(exe(*xargs))
            t1 = time.perf_counter()
            with _stage(active, "repro:readback"):
                out = np.asarray(out)
            t2 = time.perf_counter()
        except Exception as e:
            for r in live:
                r.future.set_exception(e)
            _fail_traces(live, e)
            return None
        return out, (t_m, t0, t1, t2), active

    @staticmethod
    def _client_rows(live) -> Optional[dict]:
        """{client: row count} for telemetry, or None if all anonymous."""
        counts: dict = {}
        for r in live:
            if r.client is not None:
                counts[r.client] = counts.get(r.client, 0) + 1
        return counts or None
