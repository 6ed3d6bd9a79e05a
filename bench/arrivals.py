"""Arrival schedules and their timing, for the served traffic mixes.

Derived from the repo's service benchmark (``_poisson_events``) and front-end
benchmark (``_drive_arrivals``): arrivals never wait for completions, each
request is timed from the moment it was due, and the generator records how
late it sent each one, so a starved generator is not read as a fast server.
One change: the number of arrivals is fixed by the rate and the window, so
every seed offers the same work, only at other instants.
"""

from __future__ import annotations

import time

import numpy as np

__all__ = ["poisson_arrivals", "OpenLoop"]


def poisson_arrivals(rng: np.random.Generator, rate_rps: float,
                     duration_s: float) -> np.ndarray:
    """round(rate * duration) arrival offsets in [0, duration), sorted.

    A Poisson process conditioned on its count: given N arrivals in a
    window, their instants are N independent uniform draws."""
    count = int(round(rate_rps * duration_s))
    return np.sort(rng.uniform(0.0, duration_s, count))


class OpenLoop:
    """Replays a schedule of due times against ``send(i)``.

    ``send(i)`` submits request i and returns its future.  Each request's
    sojourn runs from its due time (not its send time) to its reply, so a
    stall delays the requests behind it on the clock."""

    def __init__(self, due_s: np.ndarray):
        self.due = np.asarray(due_s, np.float64)
        self.sent = np.full(len(self.due), np.nan)
        self.done = np.full(len(self.due), np.nan)
        self.futures = [None] * len(self.due)
        self.t0 = None

    def run(self, send) -> None:
        self.t0 = t0 = time.perf_counter()
        for i, due in enumerate(self.due):
            delay = due - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            self.sent[i] = time.perf_counter() - t0
            fut = send(i)
            self.futures[i] = fut
            fut.add_done_callback(self._on_done(i))

    def _on_done(self, i):
        def cb(_fut):
            self.done[i] = time.perf_counter() - self.t0
        return cb

    def lateness_s(self) -> np.ndarray:
        """How late the generator sent each request after it was due."""
        return self.sent - self.due
