"""Model operations of the tokens the traced window's quanta advanced
(2 N per token plus attention over each slot's context) over the chip's
bf16 peak times those quanta's wall time (``serve/engine.py`` quanta)."""

from bench import counts


def read(r):
    qs = r.counters.get("quanta", [])[r.counters.get("trace_q0", 0):
                                       r.counters.get("trace_q1", 0)]
    qs = [q for q in qs if q[4] is not None]
    if not qs:
        return None
    flops = sum(counts.serve_token_flops(r.cfg, pos + t)
                for q in qs for pos, steps in q[4] for t in range(steps))
    wall = sum(q[1] for q in qs)
    return 100.0 * flops / (r.peaks["bf16_flops_per_s"] * wall)
