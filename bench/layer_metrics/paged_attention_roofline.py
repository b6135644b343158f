"""Least time the traced quanta's paged attention could take (the live
contexts' keys and values, plus q and o, over HBM bandwidth) over the
device time of the paged attention kernel (``kernels/paged_attention.py``)
on device 0."""

from bench import counts, trace_reduce


def read(r):
    t = r.reduced
    qs = r.counters.get("quanta", [])[r.counters.get("trace_q0", 0):
                                       r.counters.get("trace_q1", 0)]
    qs = [q for q in qs if q[4] is not None]
    if t is None or not qs:
        return None
    c = r.cfg
    # the kernel's output: each slot's query heads (padded), head size
    out = (f"bf16[{c['serve']['slots']},{c['padded_heads']},"
           f"{c['hidden_size'] // c['num_attention_heads']}]")
    busy = trace_reduce.kernel_s(t, out, device=0)
    if busy <= 0:
        return None
    need = 0
    for q in qs:
        for step in range(q[2]):
            ctx = [pos + step + 1 for pos, n in q[4] if step < n]
            if ctx:
                need += counts.paged_step_bytes(r.cfg, ctx)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / busy
