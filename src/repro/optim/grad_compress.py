"""Gradient compression for the data-parallel all-reduce.

A ring all-reduce is reduce_scatter + all_gather. The reduce_scatter half
must stay high precision (it sums partial gradients), but the all_gather
half broadcasts an already-reduced value — it can be int8-quantized with a
per-shard scale for a ~4x byte reduction of that half (visible as smaller
all-gather operands in the dry-run HLO):

    g -> psum_scatter(f32) -> quantize int8 -> all_gather -> dequantize

Error analysis: quantization happens after the sum, so no error accumulates
across workers; worst case is 1/2 ulp of the int8 grid, |g_shard|_max / 254.

``compressed_psum`` is used INSIDE a manual-axes (shard_map) region — the
cross-pod gradient reduction in train_step. ``compressed_allreduce`` wraps
it in its own shard_map for standalone use/tests.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _axes_size(axes) -> None:
    pass  # world size is resolved by the collectives themselves


def compressed_psum(grads, axes: Sequence[str]):
    """psum a grad pytree over manual mesh axes with int8 all-gather half.

    Must run inside a shard_map over (at least) `axes`. Small leaves that
    don't tile evenly fall back to plain psum.
    """
    axes = tuple(axes)

    w = 1
    for a in axes:
        w *= jax.lax.axis_size(a)

    def one(g):
        gf = g.astype(jnp.float32)
        flat = gf.reshape(-1)
        if flat.shape[0] % w != 0 or flat.shape[0] < 8 * w:
            return jax.lax.psum(gf, axes)
        red = jax.lax.psum_scatter(flat, axes, scatter_dimension=0, tiled=True)
        amax = jnp.max(jnp.abs(red))
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(red / scale), -127, 127).astype(jnp.int8)
        qg = jax.lax.all_gather(q, axes, axis=0, tiled=True)
        sg = jax.lax.all_gather(scale[None], axes, axis=0)
        shard = red.shape[0]
        out = (qg.reshape(w, shard) * sg.reshape(w, 1)).reshape(flat.shape)
        return out.reshape(g.shape)

    return jax.tree.map(one, grads)


def compressed_allreduce(grads, mesh, dp_axes: Sequence[str]):
    """Standalone wrapper: all-reduce replicated-view grads over dp_axes."""
    specs = jax.tree.map(lambda _: P(), grads)
    f = jax.shard_map(lambda g: compressed_psum(g, dp_axes), mesh=mesh,
                      axis_names=set(dp_axes), in_specs=(specs,),
                      out_specs=specs, check_vma=False)
    return f(grads)
