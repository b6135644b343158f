"""Compile requests (persistent-cache loads included) between the
window's opening and the last request's completion; the warm-up should
leave none (``serve/engine.py`` quanta, retunes)."""


def read(r):
    return r.counters.get("compiles_in_window")
