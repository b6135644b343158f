"""Plain reference of the 2-D Jacobi sweep (the MDMP paper's running
example), independent of the program under test.

The global grid is updated as a whole: each sweep sets every interior
column of every row to a quarter of (up + down + left + right - f), with
zero rows beyond the first and last row and the first and last column held
fixed.  The sum is taken in that order.  On several chips the grid is one
array sharded by rows and XLA moves the boundary rows itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def sweep(u, f):
    z = jnp.zeros((1, u.shape[1]), u.dtype)
    up = jnp.concatenate([z, u[:-1]], axis=0)
    down = jnp.concatenate([u[1:], z], axis=0)
    new = jnp.asarray(0.25, u.dtype) * (
        up[:, 1:-1] + down[:, 1:-1] + u[:, :-2] + u[:, 2:] - f[:, 1:-1])
    return u.at[:, 1:-1].set(new)


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def sweeps(u, f, n: int, dtype=jnp.float32):
    """``n`` sweeps from ``u``, computed in ``dtype`` (the control runs it
    in bfloat16), returned as float32."""
    u = u.astype(dtype)
    f = f.astype(dtype)
    return lax.fori_loop(0, n, lambda _, x: sweep(x, f), u
                         ).astype(jnp.float32)
