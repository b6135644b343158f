"""Jacobi 5-point stencil — Pallas TPU kernels (the paper's own kernel).

The paper's running example (Fig. 2-4) is a 2-D Jacobi sweep; MDMP manages
its halo exchange.  Within a shard the sweep is a memory-bound stencil.
Two kernels live here:

  * ``jacobi_step_pallas``      — one sweep, tiled through VMEM.  Overlapping
    (haloed) reads are expressed the TPU-idiomatic way: the four shifted
    neighbour views of ``u`` are passed as separate inputs, so every
    BlockSpec stays disjoint.  Oracle: kernels/ref.py::jacobi_step_ref.

  * ``jacobi_multistep_pallas`` — the temporally-blocked kernel: a row-tile
    (plus a k-deep halo apron) is streamed HBM->VMEM ONCE and ``k`` sweeps
    are applied in VMEM before the tile is written back, cutting HBM traffic
    ~k x.  Each sweep's valid region shrinks by one row at every tile edge
    (the classic trapezoidal / redundant-ghost scheme), which is why the
    apron must be k rows deep.  Rows pinned by physical Dirichlet
    boundaries do NOT shrink: a per-call frozen-row count (SMEM scalar,
    applied in the first/last grid block only) keeps boundary and
    out-of-domain ghost rows at their initial value through all k sweeps.
    Oracle: k applications of jacobi_step_ref.

The same trapezoid powers the distributed deep-halo schedule
(core/halo.py::jacobi_solve with k>1): there the k-row apron arrives from
ring neighbours via one halo exchange per k sweeps instead of per sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _jacobi_kernel(up_ref, down_ref, left_ref, right_ref, f_ref, o_ref):
    up = up_ref[...].astype(jnp.float32)
    down = down_ref[...].astype(jnp.float32)
    left = left_ref[...].astype(jnp.float32)
    right = right_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    o_ref[...] = (0.25 * (up + down + left + right - f)).astype(o_ref.dtype)


def jacobi_step_pallas(u: Array, f: Array, *, blk_m: int = 256,
                       blk_n: int = 256, interpret: bool = False) -> Array:
    """One Jacobi sweep on the interior of ``u`` ([M, N]); boundary
    rows/cols are Dirichlet (copied through).  f: [M, N] source term."""
    m, n = u.shape
    mi, ni = m - 2, n - 2                        # interior size
    blk_m = min(blk_m, mi)
    blk_n = min(blk_n, ni)
    assert mi % blk_m == 0 and ni % blk_n == 0, (mi, ni, blk_m, blk_n)
    grid = (mi // blk_m, ni // blk_n)

    views = (u[:-2, 1:-1], u[2:, 1:-1], u[1:-1, :-2], u[1:-1, 2:],
             f[1:-1, 1:-1])
    spec = pl.BlockSpec((blk_m, blk_n), lambda i, j: (i, j))
    interior = pl.pallas_call(
        _jacobi_kernel,
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((mi, ni), u.dtype),
        interpret=interpret,
    )(*views)
    return u.at[1:-1, 1:-1].set(interior)


# ---------------------------------------------------------------------------
# Temporally-blocked multi-sweep kernel (k sweeps per HBM round-trip)
# ---------------------------------------------------------------------------


#: Scoped VMEM the k-sweep kernel asks Mosaic for (a v5e TensorCore has
#: 128 MiB; the default scoped limit is 16 MiB), and the part of it the
#: row-block sizing may fill with its estimate of blocks and temporaries.
VMEM_LIMIT = 96 * 1024 * 1024
VMEM_BUDGET = 48 * 1024 * 1024
#: f32 tile-sized temporaries a sweep keeps live (tile, f, four shifted
#: neighbours, the update, the select)
_SWEEP_TEMPS = 8


def ksweep_trapezoid(tile: Array, f_tile: Array, k: int, frozen_top,
                     frozen_bot, roll=jnp.roll) -> Array:
    """Apply ``k`` masked Jacobi sweeps to a halo-padded row tile.

    tile, f_tile: [T, N] float32.  Columns 0 and N-1 are Dirichlet (never
    updated); rows 0 and T-1 are likewise never updated (each sweep's
    stencil cannot reach them).  ``frozen_top``/``frozen_bot`` additionally
    pin that many leading/trailing rows to their INITIAL value through all
    k sweeps — used for physical-boundary ghost rows, which must act as a
    constant Dirichlet condition rather than participate in the redundant
    ghost trapezoid.  May be traced scalars.

    Validity contract (the trapezoid): if tile rows [0, T) hold iteration-0
    values, then after this call rows [k, T-k) hold iteration-k values
    (frozen edges do not shrink).  The neighbours are whole-tile rotations
    (``roll`` is ``jnp.roll`` here and ``pltpu.roll`` in the kernel, which
    keeps every vector op tile-aligned); the wrapped rows and columns are
    exactly the ones the mask never updates.  Shared by the Pallas kernel
    and the jnp deep-halo path, and summed in the order of the bulk
    five-point update, so all three agree bit-for-bit.
    """
    t_rows, n = tile.shape
    rows = lax.broadcasted_iota(jnp.int32, (t_rows, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (t_rows, n), 1)
    upd = ((rows >= jnp.maximum(frozen_top, 1))
           & (rows < t_rows - jnp.maximum(frozen_bot, 1))
           & (cols >= 1) & (cols < n - 1))

    def sweep(_, tile):
        new = 0.25 * (roll(tile, 1, 0) + roll(tile, t_rows - 1, 0)
                      + roll(tile, 1, 1) + roll(tile, n - 1, 1) - f_tile)
        return jnp.where(upd, new, tile)

    return lax.fori_loop(0, k, sweep, tile)


def _jacobi_multistep_kernel(frozen_ref, u_ref, ulow_ref, f_ref, flow_ref,
                             o_ref, *, k: int, apron: int, rows: int):
    """One grid step: the (blk_m + 2*apron)-row tile is a blk_m-row block
    plus the 2*apron rows below it; run k sweeps in VMEM and write the
    blk_m center rows.  The frozen depths count rows of the whole padded
    array (``rows`` of them) and are moved to this tile's coordinates."""
    blk_m = o_ref.shape[0]
    t_rows = blk_m + 2 * apron
    top = pl.program_id(0) * blk_m                # tile row 0, globally
    frozen_top = frozen_ref[0, 0] - top
    frozen_bot = frozen_ref[0, 1] - (rows - top - t_rows)
    tile = jnp.concatenate([u_ref[...], ulow_ref[...]],
                           axis=0).astype(jnp.float32)
    f_tile = jnp.concatenate([f_ref[...], flow_ref[...]],
                             axis=0).astype(jnp.float32)
    out = ksweep_trapezoid(tile, f_tile, k, frozen_top, frozen_bot,
                           roll=pltpu.roll)
    o_ref[...] = out[apron:apron + blk_m].astype(o_ref.dtype)


def _row_block(m: int, n: int, apron: int, itemsize: int,
               blk_m: int | None) -> int:
    """Rows per grid step: a multiple of 2*apron (the lower-apron block
    must land on its own block grid).  Given ``blk_m`` is checked;
    otherwise the largest such divisor of the apron-rounded height whose
    blocks and sweep temporaries fit ``VMEM_BUDGET``."""
    step = 2 * apron
    if blk_m is not None:
        if blk_m % step:
            raise ValueError(f"blk_m={blk_m} must be a multiple of "
                             f"2 * apron = {step}")
        return blk_m
    rows = -(-m // step) * step

    def vmem(b):
        io = 2 * (3 * b + 2 * step) * n * itemsize   # double-buffered
        return io + _SWEEP_TEMPS * (b + step) * n * 4

    fits = [b for b in range(step, rows + 1, step)
            if rows % b == 0 and vmem(b) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"k-sweep stencil: even a {step}-row block of {n} columns "
            f"needs {vmem(step)} B of VMEM (budget {VMEM_BUDGET} B)")
    return max(fits)


def jacobi_ksweep_pallas(u_pad: Array, f_pad: Array, k: int, frozen_top,
                         frozen_bot, *, blk_m: int | None = None,
                         interpret: bool = False) -> Array:
    """k Jacobi sweeps over the center rows of a k-halo-padded block.

    u_pad, f_pad: [m + 2k, N] — the local block with its k-row apron (ghost
    slabs from ring neighbours, zeros outside the physical domain).
    Returns the [m, N] center after k sweeps; the apron is consumed by the
    trapezoidal shrink, so the result is exact (allclose to k unit sweeps).

    ``frozen_top``/``frozen_bot`` (int scalars, may be traced) pin that many
    leading/trailing PADDED rows — pass k at a non-periodic physical edge
    so the zero ghost slab behaves as a constant boundary, 0 elsewhere.

    Each grid step streams blk_m + 2*apron rows of u and f HBM->VMEM, runs
    all k sweeps on the VMEM-resident tile, and writes blk_m rows back: the
    HBM traffic per sweep drops ~k x vs. calling jacobi_step_pallas k
    times, which is the whole point of the temporal blocking.  The apron
    is k rounded up to the sublane tile (8 rows of f32), so every block
    is tile-aligned; the extra rows, and rows padding m up to a whole
    number of blocks, are zeros pinned by the frozen depths.  ``blk_m``
    defaults to the largest row block that fits the VMEM budget.
    """
    if k < 1:
        raise ValueError(f"k={k} must be >= 1")
    mp, n = u_pad.shape
    m = mp - 2 * k
    if m < 1:
        raise ValueError(f"no center rows: {mp} rows with a {k}-row apron")
    sublanes = 8 * 4 // u_pad.dtype.itemsize
    apron = -(-k // sublanes) * sublanes
    blk_m = _row_block(m, n, apron, u_pad.dtype.itemsize, blk_m)
    m_t = -(-m // blk_m) * blk_m
    top, bot = apron - k, apron - k + m_t - m
    if top or bot:
        u_pad = jnp.pad(u_pad, ((top, bot), (0, 0)))
        f_pad = jnp.pad(f_pad, ((top, bot), (0, 0)))
    frozen = jnp.stack([jnp.asarray(frozen_top, jnp.int32) + top,
                        jnp.asarray(frozen_bot, jnp.int32) + bot]
                       ).reshape(1, 2)
    low = blk_m // (2 * apron)                    # lower-apron block stride
    blk_spec = pl.BlockSpec((blk_m, n), lambda i: (i, 0))
    low_spec = pl.BlockSpec((2 * apron, n), lambda i: ((i + 1) * low, 0))
    kernel = functools.partial(_jacobi_multistep_kernel, k=k, apron=apron,
                               rows=m_t + 2 * apron)
    out = pl.pallas_call(
        kernel,
        grid=(m_t // blk_m,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # frozen depths
            blk_spec, low_spec,                          # u
            blk_spec, low_spec,                          # f
        ],
        out_specs=blk_spec,
        out_shape=jax.ShapeDtypeStruct((m_t, n), u_pad.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(frozen, u_pad, u_pad, f_pad, f_pad)
    return out if m_t == m else out[:m]


def jacobi_multistep_pallas(u: Array, f: Array, *, k: int,
                            blk_m: int | None = None,
                            interpret: bool = False) -> Array:
    """``k`` Jacobi sweeps on the interior of ``u`` ([M, N]) in ONE HBM
    round-trip — temporally-blocked equivalent of calling
    ``jacobi_step_pallas`` k times (boundary rows/cols Dirichlet, same
    oracle: k x jacobi_step_ref).

    Implementation: pad with k zero rows top and bottom, freeze the padding
    plus the true boundary row (k + 1 rows) so the Dirichlet condition
    survives all k sweeps, and run the trapezoidal slab kernel.
    """
    z = jnp.zeros((k,) + u.shape[1:], u.dtype)
    u_pad = jnp.concatenate([z, u, z], axis=0)
    f_pad = jnp.concatenate([z, f, z], axis=0)
    return jacobi_ksweep_pallas(u_pad, f_pad, k, k + 1, k + 1,
                                blk_m=blk_m, interpret=interpret)
