"""Profiling hooks: optional jax.profiler integration.

Third observability pillar (docs/observability.md).  Two pieces:

  * :func:`profile_session` -- ``with obs.profile_session(dir):``
    captures a jax profiler trace (viewable in TensorBoard / Perfetto)
    for the enclosed block.  Wired into ``benchmarks/run.py --profile``.
  * :func:`annotate` -- named ``repro:<stage>`` trace annotations around
    the host stages of a served request (decode, submit, coalesce,
    marshal, device_execute, readback, respond, reply, ...), so a
    profiler timeline shows what every host thread was doing while the
    device sat idle.  Call sites guard with :func:`is_active` so the
    annotation object is never constructed outside a capture.

:func:`is_active` reads the profiler's own state, so a capture started
any way -- ``profile_session``, a bare ``jax.profiler.start_trace``, a
profiler server -- lights the annotations.  jax is imported lazily, on
the first call, so the obs package stays dependency-free until then.  A
capture that cannot start raises: a run that was asked for a trace and
silently produced none would be read as measured.
"""

from __future__ import annotations

import contextlib

__all__ = ["profile_session", "annotate", "is_active"]

# jax.profiler.TraceAnnotation.is_enabled, bound on the first is_active()
_is_enabled = None


def _bind():
    global _is_enabled
    try:
        from jax.profiler import TraceAnnotation
        _is_enabled = TraceAnnotation.is_enabled
    except ImportError:         # no jax: nothing can be capturing
        _is_enabled = lambda: False  # noqa: E731
    return _is_enabled


def is_active() -> bool:
    """True while a profiler capture is running in this process, however
    it was started (one call into the profiler, ~0.1 us -- safe to check
    per request on the serving hot path)."""
    f = _is_enabled
    if f is None:
        f = _bind()
    return f()


@contextlib.contextmanager
def profile_session(log_dir: str, *, create_perfetto_link: bool = False):
    """Capture a jax profiler trace for the enclosed block into log_dir.

    A second capture while one is running is rejected (the jax profiler
    is a process-global singleton), and so is a trace that fails to
    start.
    """
    from jax import profiler as _jp
    if is_active():
        raise RuntimeError("a profiler capture is already running")
    _jp.start_trace(str(log_dir), create_perfetto_link=create_perfetto_link)
    try:
        yield log_dir
    finally:
        _jp.stop_trace()


def annotate(name: str, **meta):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` while a capture
    is running, a no-op context otherwise.  ``meta`` is stored as the
    event's stats (the name stays clean).  Callers on hot paths should
    gate construction on :func:`is_active` themselves; this fallback
    exists for call sites that don't."""
    if is_active():
        from jax import profiler as _jp
        return _jp.TraceAnnotation(name, **meta)
    return contextlib.nullcontext()
