"""Parallelism context threaded through model code.

Keeps the model definitions mesh-agnostic: with ``ctx=None`` (unit tests,
single host) every layer runs its dense/local fallback; with a production
mesh the context enables expert parallelism (shard_map over the model axis)
and sequence-sharded decode caches.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Optional[object] = None          # jax.sharding.Mesh
    dp_axes: Tuple[str, ...] = ()          # mesh axes the batch is sharded over
    tp_axis: Optional[str] = None          # tensor/expert-parallel axis
    seq_shard_cache: bool = False          # decode KV cache sharded over tp_axis

    @property
    def tp(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def dp(self) -> Tuple[str, ...] | None:
        return self.dp_axes if self.dp_axes else None

    def shard_map(self, f, in_specs, out_specs):
        """Manual collectives over the tp axis only; other axes stay auto."""
        assert self.mesh is not None and self.tp_axis is not None
        return jax.shard_map(f, mesh=self.mesh, axis_names={self.tp_axis},
                             in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)


NO_CTX = ParallelCtx()


def batch_spec(ctx: Optional[ParallelCtx], *rest) -> P:
    """PartitionSpec with the batch dim over dp axes, remaining dims as given."""
    if ctx is None or not ctx.dp_axes:
        return P(*((None,) + rest))
    return P(*((ctx.dp_axes,) + rest))
