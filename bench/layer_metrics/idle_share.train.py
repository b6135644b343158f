"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 1 - union of busy intervals / window."""

from bench import trace_reduce


def read(r):
    if r.reduced is None or not r.reduced.devices:
        return None
    return 100.0 * trace_reduce.idle_share(r.reduced)
