"""Server-side protocol time per request in the traced window: the
connection reader's ``repro:decode`` and the dispatch worker's
``repro:reply`` spans, summed and divided by the requests answered (one
``reply`` each) in the window (transport layer; moves served_rps).

Fields: microseconds per request of ``decode``, ``reply`` and ``submit``
(the scheduler's submit, from the reader thread), and of the in-process
load generator's ``client_send`` and ``client_recv``: how much of the
interpreter the server shares with its clients takes."""

from bench import host_spans as hs

STAGES = ("decode", "reply", "submit", "client_send", "client_recv")


def read(run):
    if run.timeline is None:
        return None
    lo, hi = run.window_ns
    by_stage = {s: hs.spans(run.timeline, s, lo, hi) for s in STAGES}
    answered = len(by_stage["reply"])
    if not answered:
        return None
    per_req = {s: hs.total_ns(iv) * 1e-3 / answered
               for s, iv in by_stage.items()}
    return {"value": per_req["decode"] + per_req["reply"],
            **{f"{s}_us": us for s, us in per_req.items()},
            "requests": answered}
