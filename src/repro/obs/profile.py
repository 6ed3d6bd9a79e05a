"""Profiling hooks: optional jax.profiler integration.

Third observability pillar (docs/observability.md).  Two pieces:

  * :func:`profile_session` -- ``with obs.profile_session(dir):``
    captures a jax profiler trace (viewable in TensorBoard / Perfetto)
    for the enclosed block.  Wired into ``benchmarks/run.py --profile``.
  * :func:`annotate` -- named trace annotations around plan executions
    so device timelines show *which* plan/bucket a kernel belongs to.
    Dispatch guards with :func:`is_active` (a plain bool read) so the
    annotation context manager is never even constructed outside a
    capture session.

jax is imported lazily, so the obs package stays dependency-free until
a capture is asked for.  A capture that cannot start raises: a run that
was asked for a trace and silently produced none would be read as
measured.
"""

from __future__ import annotations

import contextlib
import threading

__all__ = ["profile_session", "annotate", "is_active"]

_active = False
_lock = threading.Lock()


def is_active() -> bool:
    """True while a profile_session capture is running (plain bool read
    -- safe to check per-batch on the dispatch hot path)."""
    return _active


@contextlib.contextmanager
def profile_session(log_dir: str, *, create_perfetto_link: bool = False):
    """Capture a jax profiler trace for the enclosed block into log_dir.

    Nested/concurrent sessions are rejected (the jax profiler is a
    process-global singleton), and so is a trace that fails to start.
    """
    global _active
    from jax import profiler as _jp
    with _lock:
        if _active:
            raise RuntimeError("a profile_session is already active")
        _active = True
    try:
        _jp.start_trace(str(log_dir),
                        create_perfetto_link=create_perfetto_link)
    except BaseException:
        with _lock:
            _active = False
        raise
    try:
        yield log_dir
    finally:
        try:
            _jp.stop_trace()
        finally:
            with _lock:
                _active = False


def annotate(name: str):
    """A TraceAnnotation context manager naming the enclosed device work.

    Returns a real ``jax.profiler.TraceAnnotation`` while a capture is
    active, a no-op context otherwise.  Callers on hot paths should gate
    construction on :func:`is_active` themselves; this fallback exists
    for call sites that don't.
    """
    if _active:
        try:
            from jax import profiler as _jp
            return _jp.TraceAnnotation(name)
        except Exception:
            pass
    return contextlib.nullcontext()
