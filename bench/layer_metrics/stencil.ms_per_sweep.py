"""Device milliseconds of the k-sweep stencil kernel per Jacobi sweep in
the traced window, averaged over the chips (``kernels/stencil.py``)."""

from bench import trace_reduce


def read(r):
    t, n = r.reduced, r.counters.get("traced_dispatches", 0)
    if t is None or not n:
        return None
    # the kernel writes one chip's block of rows
    block = f"f32[{r.cfg['rows_per_chip']},{r.cfg['cols']}]"
    busy = trace_reduce.kernel_s(t, block)
    if busy <= 0:
        return None
    return busy / len(t.devices) / (n * r.counters["iters"]) * 1e3
