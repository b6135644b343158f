"""Useful slot-steps over slots times steps dispatched, over the quanta
whose results the host held inside the window (``serve/engine.py``,
``serve/scheduler.py``)."""


def read(r):
    qs = r.counters.get("quanta", [])[:r.counters.get("window_q1", 0)]
    if not qs:
        return None
    slots = r.counters["slots"]
    return 100.0 * sum(q[3] for q in qs) / sum(q[2] * slots for q in qs)
