"""Managed halo exchange — the paper's running Jacobi example, TPU-native.

The paper's Figure 2 (bulk: exchange full halos, then compute) vs Figure 3
(intermingled: compute boundary rows first, send each as soon as written,
compute the interior while messages fly).  Here:

  * ``halo_exchange``       — bulk: two ppermutes of the full halo slabs.
  * ``halo_exchange_overlapped`` — the Figure-3 schedule: boundary slabs are
    produced and sent first; the interior compute is issued *between* the
    permute-starts and the halo consumption, so XLA's async collective
    engine overlaps the DMA with interior compute.  Semantically identical.
  * ``jacobi_solve(mode="aggregated", k=...)`` — the paper's third knob,
    message AGGREGATION: exchange a k-row slab once per k sweeps instead of
    a 1-row slab every sweep, and redundantly compute the ghost trapezoid
    (kernels/stencil.py::ksweep_trapezoid).  Per sweep this pays

        comm:  2*alpha/k + 2*cols*B/link_bw      (k x fewer messages,
                                                  same halo bytes)
        mem:   ~3*rows*cols*B/(k*hbm_bw)         (k sweeps per HBM
                                                  round-trip of the tile)
        flops: (rows + 2*(k-1))*cols*c/peak      (redundant ghost rows)

    so aggregation wins whenever per-message latency (alpha) or HBM
    streaming dominates the small redundant-compute tax — exactly the
    managed decision core/cost_model.py::decide_halo_aggregation makes.

Both operate on a 1-D process-grid decomposition (rows sharded over one
mesh axis) of an n-D local block, matching the paper's benchmark.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs.tracer import dispatch_span

Array = jax.Array


def _edge_perms(n: int) -> tuple[list, list]:
    fwd = [(i, i + 1) for i in range(n - 1)]   # non-periodic, like the paper
    bwd = [(i + 1, i) for i in range(n - 1)]
    return fwd, bwd


def halo_exchange(x: Array, axis_name: str, *, halo: int = 1,
                  periodic: bool = False) -> tuple[Array, Array]:
    """Exchange ``halo`` rows with ring neighbours along ``axis_name``.

    Returns ``(lo_halo, hi_halo)`` — the rows received from the previous /
    next rank (zeros at the boundary when non-periodic, matching
    MPI_PROC_NULL semantics in the paper's code).
    """
    n = lax.psum(1, axis_name)
    if n == 1:
        if periodic:
            return x[-halo:], x[:halo]
        z = jnp.zeros((halo,) + x.shape[1:], x.dtype)
        return z, z
    if periodic:
        fwd = [(i, (i + 1) % n) for i in range(n)]
        bwd = [((i + 1) % n, i) for i in range(n)]
    else:
        fwd, bwd = _edge_perms(n)
    # send my last rows forward -> neighbour's lo halo
    lo = lax.ppermute(x[-halo:], axis_name, fwd)
    # send my first rows backward -> neighbour's hi halo
    hi = lax.ppermute(x[:halo], axis_name, bwd)
    return lo, hi


def jacobi_step_bulk(u: Array, f: Array, axis_name: str,
                     periodic: bool = False) -> Array:
    """Paper Figure 2: exchange halos, then the 5-point update — comm and
    compute fully separated."""
    lo, hi = halo_exchange(u, axis_name, periodic=periodic)
    up = jnp.concatenate([lo, u, hi], axis=0)
    return _five_point(up, f)


def jacobi_step_overlapped(u: Array, f: Array, axis_name: str,
                           periodic: bool = False) -> Array:
    """Paper Figure 3: start the halo messages, compute the interior while
    they are in flight, then compute the two boundary rows that need the
    halos.  Identical result, intermingled schedule."""
    lo, hi = halo_exchange(u, axis_name,          # permute-starts issue here
                           periodic=periodic)
    # Interior rows (2..m-3 of the update) depend only on local data: XLA
    # schedules this compute between permute-start and permute-done.
    m = u.shape[0]
    up_int = u                                     # rows 0..m-1 available
    interior = 0.25 * (up_int[:-2, 1:-1] + up_int[2:, 1:-1]
                       + up_int[1:-1, :-2] + up_int[1:-1, 2:]
                       - f[1:-1, 1:-1])            # rows 1..m-2
    # Boundary rows 0 and m-1 need lo/hi halos (consume the messages last).
    row0 = 0.25 * (lo[:, 1:-1] + u[1:2, 1:-1]
                   + u[0:1, :-2] + u[0:1, 2:] - f[0:1, 1:-1])
    rowm = 0.25 * (u[m - 2:m - 1, 1:-1] + hi[:, 1:-1]
                   + u[m - 1:m, :-2] + u[m - 1:m, 2:] - f[m - 1:m, 1:-1])
    core = jnp.concatenate([row0, interior, rowm], axis=0)
    # Columns 0 and -1 are fixed boundary (Dirichlet), copied through.
    out = u.at[:, 1:-1].set(core)
    return out


def _five_point(up: Array, f: Array) -> Array:
    """5-point Jacobi update on a halo-padded block ``up`` ([m+2, n]),
    Dirichlet columns."""
    new = 0.25 * (up[:-2, 1:-1] + up[2:, 1:-1]
                  + up[1:-1, :-2] + up[1:-1, 2:] - f[:, 1:-1])
    out = up[1:-1].at[:, 1:-1].set(new)
    return out


# ---------------------------------------------------------------------------
# Aggregated (deep-halo, temporally-blocked) schedule — k sweeps/exchange
# ---------------------------------------------------------------------------


def _frozen_depths(axis_name: str, k: int, periodic: bool):
    """Ghost-slab rows outside the physical domain must stay constant
    (zeros) through all k sweeps; rows from a real neighbour participate in
    the redundant trapezoid instead.  Returns (frozen_top, frozen_bot) row
    counts as traced scalars."""
    if periodic:
        return jnp.int32(0), jnp.int32(0)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    frozen_top = jnp.where(idx == 0, k, 0)
    frozen_bot = jnp.where(idx == n - 1, k, 0)
    return frozen_top, frozen_bot


def jacobi_step_aggregated(u: Array, f: Array, flo: Array, fhi: Array,
                           axis_name: str, k: int, *,
                           periodic: bool = False, engine: str = "jnp",
                           blk_m: int | None = None,
                           interpret: bool | None = None) -> Array:
    """k Jacobi sweeps for ONE k-row halo exchange (the aggregation knob).

    ``flo``/``fhi`` are the source term's k-row ghost slabs — f is
    iteration-invariant, so the caller exchanges it once per solve, not per
    step (see ``jacobi_solve``).

    engine="jnp" runs the trapezoid as plain XLA ops (portable; what the
    CPU-hosted benchmarks measure); engine="pallas" runs the VMEM-resident
    multi-sweep kernel (kernels/stencil.py) so the k x HBM-traffic saving
    is realised on TPU.  Both share ksweep_trapezoid, so they agree
    bit-for-bit.  ``blk_m`` (rows per kernel block) defaults to the largest
    that fits VMEM; ``interpret`` defaults to the kernels' mode
    (``kernels.ops._pallas_mode``): compiled on TPU, interpreted elsewhere.
    """
    from repro.kernels.ops import _pallas_mode
    from repro.kernels.stencil import jacobi_ksweep_pallas, ksweep_trapezoid

    lo, hi = halo_exchange(u, axis_name, halo=k, periodic=periodic)
    u_pad = jnp.concatenate([lo, u, hi], axis=0)
    f_pad = jnp.concatenate([flo, f, fhi], axis=0)
    frozen_top, frozen_bot = _frozen_depths(axis_name, k, periodic)
    if engine == "pallas":
        if interpret is None:
            interpret = _pallas_mode() != "on"
        return jacobi_ksweep_pallas(u_pad, f_pad, k, frozen_top, frozen_bot,
                                    blk_m=blk_m, interpret=interpret)
    out = ksweep_trapezoid(u_pad.astype(jnp.float32),
                           f_pad.astype(jnp.float32), k,
                           frozen_top, frozen_bot)
    return out[k:-k].astype(u.dtype)


def jacobi_solve(u0: Array, f: Array, axis_name: str, iters: int,
                 mode: str = "bulk", *, k: int = 1,
                 periodic: bool = False, engine: str = "jnp",
                 blk_m: int | None = None,
                 interpret: bool | None = None) -> Array:
    """Run ``iters`` Jacobi sweeps with the selected halo schedule.

    mode="bulk"        — paper Fig 2: 1-row exchange, then compute.
    mode="interleaved" — paper Fig 3: 1-row exchange overlapped with the
                         interior compute.
    mode="aggregated"  — deep halos: one k-row exchange per k sweeps plus a
                         redundant ghost trapezoid; pick ``k`` with
                         cost_model.decide_halo_aggregation (k=1 degrades
                         exactly to bulk).  Message count drops from
                         2*iters to 2*ceil(iters/k) + 2 (the +2 is the
                         one-time f-ghost exchange).
    """
    # one span per solve dispatch; scale=iters so dur/scale is measured
    # per-sweep seconds, the unit decide_halo_aggregation predicts
    row_bytes = int(u0.size // max(1, u0.shape[0])) * u0.dtype.itemsize
    with dispatch_span("halo.solve", u0, op="halo_aggregation",
                       axis=axis_name, nbytes=k * row_bytes, mode=mode,
                       k=k, scale=iters, buffer="halo_rows"):
        return _jacobi_solve(u0, f, axis_name, iters, mode, k=k,
                             periodic=periodic, engine=engine,
                             blk_m=blk_m, interpret=interpret)


def _jacobi_solve(u0: Array, f: Array, axis_name: str, iters: int,
                  mode: str = "bulk", *, k: int = 1,
                  periodic: bool = False, engine: str = "jnp",
                  blk_m: int | None = None,
                  interpret: bool | None = None) -> Array:
    if mode == "aggregated":
        k = max(1, int(k))
        u = u0
        blocks, rem = divmod(iters, k)
        if blocks > 0 and k > u0.shape[0]:
            raise ValueError(
                f"aggregation factor k={k} exceeds the local block height "
                f"{u0.shape[0]}: the ghost trapezoid would swallow the "
                f"whole shard (cost_model.decide_halo_aggregation caps k)")
        if blocks > 0:
            # f is iteration-invariant: ship its ghost slabs once.
            flo, fhi = halo_exchange(f, axis_name, halo=k, periodic=periodic)

            def body(_, u):
                return jacobi_step_aggregated(
                    u, f, flo, fhi, axis_name, k, periodic=periodic,
                    engine=engine, blk_m=blk_m, interpret=interpret)

            u = lax.fori_loop(0, blocks, body, u)

        def tail(_, u):
            return jacobi_step_bulk(u, f, axis_name, periodic)

        return lax.fori_loop(0, rem, tail, u)

    step = {"bulk": jacobi_step_bulk,
            "interleaved": jacobi_step_overlapped}[mode]

    def body(_, u):
        return step(u, f, axis_name, periodic)

    return lax.fori_loop(0, iters, body, u0)
