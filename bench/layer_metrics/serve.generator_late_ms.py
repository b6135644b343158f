"""95th percentile of how late the generator submitted requests after
they were due, in milliseconds: a starved generator would read as a fast
server."""

from bench import harness


def read(r):
    late = r.counters.get("late_s")
    if not late:
        return None
    return 1e3 * harness.quantile(late, 0.95)
