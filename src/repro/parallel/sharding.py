"""Sharding rules and mesh context for the explicit-collectives runtime.

The whole framework runs model code inside ONE ``shard_map`` over the full
mesh — no collective is ever inserted by GSPMD, every byte that crosses a
link goes through an MDMP managed op (core/managed.py).  That is the
paper's contract ("the user declares communication, the runtime manages
it") enforced architecturally.

Parameter layout (identical for train and serve — no resharding between
them):

  * every TP-partitioned dimension (heads, d_ff, vocab, experts) is sharded
    over the ``model`` axis;
  * one remaining large dimension (usually d_model) is sharded over the
    ``data`` axis — this is the FSDP/ZeRO-3 shard, gathered-on-use in
    training, contracted-in-place in decode;
  * the ``pod`` axis (multi-pod mesh) replicates parameters: pure DP with
    hierarchical gradient reduction, or pipeline stages when enabled.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def smap(fn: Callable, mesh: Mesh, in_specs, out_specs) -> Callable:
    """``jax.shard_map`` with VMA checking off (ring collectives produce
    values the replication checker cannot infer; correctness is covered by
    tests).  Every shard_map in the repo goes through here."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def padded(n: int, m: int) -> tuple[int, int]:
    """(padded_size, pad_amount)."""
    p = pad_to_multiple(n, m)
    return p, p - n


# ---------------------------------------------------------------------------
# Mesh context
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """Static view of the mesh as seen by model code inside shard_map.

    Axis conventions: ``data`` = FSDP + batch, ``model`` = TP/EP/SP,
    ``pod`` = cross-pod DP (or pipeline stages).  Sizes are static.
    """
    axis_sizes: dict[str, int]          # e.g. {"pod": 2, "data": 16, "model": 16}
    mdmp_mode: str = "auto"             # threaded into managed collectives

    @property
    def tp(self) -> int:
        return self.axis_sizes.get("model", 1)

    @property
    def dp(self) -> int:
        return self.axis_sizes.get("data", 1)

    @property
    def pods(self) -> int:
        return self.axis_sizes.get("pod", 1)

    @property
    def has_pod(self) -> bool:
        return "pod" in self.axis_sizes

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return (("pod", "data") if self.has_pod else ("data",))

    @property
    def batch_shards(self) -> int:
        return self.dp * self.pods

    @property
    def all_axes(self) -> tuple[str, ...]:
        return tuple(self.axis_sizes.keys())

    def local_batch(self, global_batch: int) -> int:
        assert global_batch % self.batch_shards == 0, (
            f"global batch {global_batch} not divisible by "
            f"{self.batch_shards} batch shards")
        return global_batch // self.batch_shards

    @staticmethod
    def from_mesh(mesh: Mesh, mdmp_mode: str = "auto") -> "MeshCtx":
        return MeshCtx(axis_sizes=dict(zip(mesh.axis_names,
                                           mesh.devices.shape)),
                       mdmp_mode=mdmp_mode)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

#: logical dimension names -> mesh axis they shard over (None = replicated)
LOGICAL_RULES: dict[str, str | None] = {
    "layers": None,        # scan dimension, never sharded
    "embed": "data",       # d_model rows: the FSDP shard
    "embed_nofsdp": None,  # d_model when the tensor is tiny (norms)
    "heads": "model",
    "kv_heads": None,      # replicated (GQA kv < tp; see DESIGN.md)
    "ff": "model",
    "vocab": "model",
    "experts": "model",    # EP: experts sharded by expert id
    "expert_ff": None,
    "ssm_heads": "model",
    "inner": "model",      # SSM d_inner (= heads * headdim), head-sharded
    "conv": None,
    "state": None,
    "frames": None,
    "null": None,
}


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Global shape + logical axes of one parameter."""
    shape: tuple[int, ...]
    logical: tuple[str, ...]
    dtype: Any = None

    def pspec(self) -> P:
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)
        return P(*[LOGICAL_RULES[l] for l in self.logical])

    def local_shape(self, ctx: MeshCtx) -> tuple[int, ...]:
        out = []
        for s, l in zip(self.shape, self.logical):
            ax = LOGICAL_RULES[l]
            n = ctx.axis_sizes.get(ax, 1) if ax else 1
            assert s % n == 0, f"dim {l}={s} not divisible by {ax}={n}"
            out.append(s // n)
        return tuple(out)


def infer_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    """ParamSpec tree -> NamedSharding tree (for jit in_shardings)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s.pspec()), spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def spec_pspecs(spec_tree: Any) -> Any:
    """ParamSpec tree -> PartitionSpec tree (for shard_map in_specs)."""
    return jax.tree.map(lambda s: s.pspec(), spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def global_shape_dtypes(spec_tree: Any, default_dtype) -> Any:
    """ParamSpec tree -> ShapeDtypeStruct tree (dry-run stand-ins)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or default_dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
