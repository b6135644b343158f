"""Share of the traced window in which a collective ran on device 0 and
no other operation ran there: the halo exchange that compute did not hide
(``core/halo.py``)."""

from bench import trace_reduce


def read(r):
    t = r.reduced
    if t is None or len(t.devices) < 2:
        return None
    _, exposed = trace_reduce.exposed_collective_s(t, 0)
    return 100.0 * exposed / (t.window_ns / 1e9)
