"""Driver of the ``halo`` traffic kind: the MDMP paper's Jacobi solve.

The grid of the configuration is made on the chips from the seed, split by
rows over one mesh axis.  Each dispatch is one managed ``jacobi_solve`` of
``sweeps_per_check`` sweeps (schedule ``aggregated``, k from
``managed.resolve_halo_aggregation``, the Pallas k-sweep kernel), after
which the host reads back the norm of the change, as a solver that checks
convergence does.  The window dispatches back to back for ``--seconds``.

``correct``: one dispatch of the window, drawn from the seed, keeps its
input and output; once the window has closed the plain reference runs the
same sweeps from that input, and the largest difference, as a share of the
largest value of the reference's result, is compared with its limit.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness
from bench.harness import Run, span

#: largest |program - reference| / max |reference| over the checked grid:
#: sound runs read 0 (the same float32 operations in the same order) over
#: 16 seeds, the bfloat16 control 0.026-0.030 (PERF.md, section 2)
MAX_REL_ERR = 1e-4


def build(r: Run):
    """The seeded grid and source on the cell's mesh, the compiled solve
    (or the control in its place) and the readback of the change."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import halo, managed
    from repro.parallel.sharding import smap

    cfg = r.cfg
    n_dev = len(r.devices)
    rows, cols = cfg["rows_per_chip"] * n_dev, cfg["cols"]
    iters = int(cfg["sweeps_per_check"])
    mesh = Mesh(np.array(r.devices), ("x",))
    spec = P("x", None)
    sh = NamedSharding(mesh, spec)
    make = jax.jit(lambda key: tuple(
        jax.random.normal(k, (rows, cols), jnp.float32)
        for k in jax.random.split(key)), out_shardings=(sh, sh))
    u, f = make(harness.jax_key(r.seed, 1))
    k = managed.resolve_halo_aggregation("x", n_dev, rows // n_dev, cols,
                                         dtype_bytes=4).k
    if r.control:
        from bench.reference import jacobi
        solve = jax.jit(lambda a, b: jacobi.sweeps(a, b, iters,
                                                   jnp.bfloat16),
                        out_shardings=sh)
    else:
        solve = jax.jit(smap(
            lambda a, b: halo.jacobi_solve(a, b, "x", iters, "aggregated",
                                           k=k, engine="pallas"),
            mesh, in_specs=(spec, spec), out_specs=spec))
    change = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    r.counters.update(k=k, rows=rows, cols=cols, iters=iters, chips=n_dev)
    return u, f, solve, change


def run(r: Run) -> None:
    u, f, solve, change = build(r)
    iters = r.counters["iters"]
    # set-up ends with one dispatch of the window's own call: it compiles
    # (or loads) the solve and the readback
    with span("bench.halo.warmup"):
        u_next = solve(u, f)
        float(change(u_next, u))
    u = u_next
    pick = harness.rng(r.seed, 2)
    trace_from = r.seconds - float(r.traffic.get("trace_seconds", r.seconds))
    counter = harness.CompileCounter()
    r.setup_done()
    counter.armed = True
    t0 = time.perf_counter()
    n = 0
    traced_from = None
    kept = None
    norms = []
    while True:
        now = time.perf_counter() - t0
        if now >= r.seconds:
            break
        if r.trace and traced_from is None and now >= trace_from:
            r.start_trace()
            traced_from = n
        with span("bench.halo.dispatch"):
            u_next = solve(u, f)
        with span("bench.halo.readback"):
            norms.append(float(change(u_next, u)))
        # one dispatch of the window, uniform over all of them (reservoir)
        if pick.random() * (n + 1) < 1.0:
            kept = (n, u, u_next)
        u = u_next
        n += 1
    t1 = time.perf_counter()
    counter.armed = False
    r.stop_trace()
    r.attempted = n
    r.metric("halo_sweeps_per_s", n * iters / (t1 - t0), "sweeps/s")
    r.counters.update(dispatches=n, window_s=t1 - t0,
                      traced_dispatches=(n - traced_from
                                         if traced_from is not None else 0),
                      compiles_in_window=counter.count)
    r.peak_bytes = harness.peak_bytes(r.devices)
    del u, u_next
    verify(r, kept, f, norms)


def verify(r: Run, kept, f, norms) -> None:
    import jax
    import jax.numpy as jnp
    from bench.reference import jacobi
    if kept is None or not np.all(np.isfinite(norms)):
        r.failed = r.attempted
        r.check("halo_max_rel_err", float("inf"), MAX_REL_ERR)
        return
    idx, u_in, u_out = kept
    want = jacobi.sweeps(u_in, f, r.counters["iters"])
    err = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b))
                  / jnp.max(jnp.abs(b)))(u_out, want)
    r.counters["checked_dispatch"] = idx
    r.check("halo_max_rel_err", float(err), MAX_REL_ERR)
