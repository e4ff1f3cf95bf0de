"""Fused AMS-Quant dequantize + matmul Pallas TPU kernel (paper §3.2/§3.3).

TPU adaptation of the paper's CUDA "fast restoration via bit operations":

  * packed int32 bit-planes stream HBM->VMEM through BlockSpec-tiled,
    grid-pipelined DMAs (the TPU analogue of coalesced global loads);
  * per-tile SHIFT/AND/OR restore sign/exponent/mantissa (+ shared LSB) into
    an f32 bit pattern in VREGs — no lookup tables, no scalar loops;
  * the restored bf16 tile feeds the MXU; f32 accumulation lives in a VMEM
    scratch across the K grid dimension; channel scales are folded in once
    at the final K step (they are per-output-channel, so they commute with
    the K-sum).

Grid: (B_blocks, N_blocks, K_blocks), K innermost ("arbitrary") so each
(b, n) accumulator is revisited consecutively; B/N are parallel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PackLayout
from repro.kernels.tuning import vmem_usage

# --------------------------------------------------------------------------
# In-kernel bit restoration (shared by both containers AND by the paged
# KV-cache attention kernel in repro.cache.paged_attention)
# --------------------------------------------------------------------------
def decode_codes_to_f32(codes: jnp.ndarray, fmt) -> jnp.ndarray:
    """SHIFT/AND/OR restoration of full codes -> f32 values (bit-exact)."""
    m, e, bias = fmt.man_bits, fmt.exp_bits, fmt.bias
    M = codes & ((1 << m) - 1)
    E = (codes >> m) & ((1 << e) - 1)
    S = (codes >> (m + e)) & 1
    sign_bits = S << 31
    # normal: reassemble an IEEE f32 bit pattern directly
    norm_bits = ((E - bias + 127) << 23) | (M << (23 - m)) | sign_bits
    v_norm = pltpu.bitcast(norm_bits.astype(jnp.int32), jnp.float32)
    # subnormal (E==0): value = M * 2^(1-bias-m); exact int->f32 convert
    v_sub = M.astype(jnp.float32) * np.float32(2.0 ** (1 - bias - m))
    v_sub = jnp.where(S == 1, -v_sub, v_sub)
    return jnp.where(E == 0, v_sub, v_norm)


def _unpack_planes(hi, lsb, lay: PackLayout, bk: int, bn: int) -> jnp.ndarray:
    """planes container -> full codes [bk, bn]."""
    k = lay.scheme.k
    hb, pw = lay.hi_bits, lay.per_word
    mask = (1 << hb) - 1
    parts = [(hi >> (hb * j)) & mask for j in range(pw)]
    hi_codes = jnp.stack(parts, axis=1).reshape(bk, bn)
    if k == 1:
        return hi_codes
    gbits = jnp.stack([(lsb >> j) & 1 for j in range(32)], axis=1)
    gbits = gbits.reshape(bk // k, 1, bn)
    lsb_full = jnp.broadcast_to(gbits, (bk // k, k, bn)).reshape(bk, bn)
    return (hi_codes << 1) | lsb_full


def _unpack_fp533(word, bk: int, bn: int) -> jnp.ndarray:
    """fp533 fused container -> full e2m3 codes [bk, bn].

    Each int32 = two half-words; each half = 3x5-bit high segments + 1 shared
    LSB (bit 15). 6 weights / word.
    """
    out = []
    for h in range(2):
        half = (word >> (16 * h)) & 0xFFFF
        shared = (half >> 15) & 1
        for j in range(3):
            out.append((((half >> (5 * j)) & 0x1F) << 1) | shared)
    codes = jnp.stack(out, axis=1)  # [bk//6, 6, bn] in position order
    return codes.reshape(bk, bn)


# --------------------------------------------------------------------------
# Kernel bodies
# --------------------------------------------------------------------------
def _kernel_planes(x_ref, hi_ref, lsb_ref, scale_ref, o_ref, acc_ref, *,
                   lay: PackLayout, bk: int, bn: int, nk: int, out_dtype):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_planes(hi_ref[...], lsb_ref[...], lay, bk, bn)
    w = decode_codes_to_f32(codes, lay.scheme.base).astype(jnp.bfloat16)
    x = x_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)).astype(out_dtype)


def _kernel_fp533(x_ref, hi_ref, scale_ref, o_ref, acc_ref, *,
                  lay: PackLayout, bk: int, bn: int, nk: int, out_dtype):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_fp533(hi_ref[...], bk, bn)
    w = decode_codes_to_f32(codes, lay.scheme.base).astype(jnp.bfloat16)
    x = x_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)).astype(out_dtype)


# --------------------------------------------------------------------------
# pallas_call wrapper
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("lay", "B", "K", "N", "bb", "bk", "bn", "out_dtype", "interpret"),
)
def ams_matmul_padded(
    x, hi, lsb, scale, *, lay: PackLayout, B: int, K: int, N: int,
    bb: int, bk: int, bn: int, out_dtype=jnp.float32, interpret: bool = False,
):
    """Core pallas_call on pre-padded operands.

    x: [B, K] (B % bb == 0, K % bk == 0), hi/lsb padded to matching rows,
    scale: [1, N] (N % bn == 0). The compiler's VMEM limit is the tile's
    `tuning.vmem_usage` estimate, so the estimate is held true on chip.
    """
    nb, nn, nk = B // bb, N // bn, K // bk
    pw = lay.per_word
    hi_rows_per_bk = bk // pw

    x_spec = pl.BlockSpec((bb, bk), lambda b, n, k: (b, k))
    hi_spec = pl.BlockSpec((hi_rows_per_bk, bn), lambda b, n, k: (k, n))
    scale_spec = pl.BlockSpec((1, bn), lambda b, n, k: (0, n))
    out_spec = pl.BlockSpec((bb, bn), lambda b, n, k: (b, n))
    grid = (nb, nn, nk)
    scratch = [pltpu.VMEM((bb, bn), jnp.float32)]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_usage(lay, bb, bk, bn),
    )

    if lay.container == "fp533":
        kernel = functools.partial(
            _kernel_fp533, lay=lay, bk=bk, bn=bn, nk=nk, out_dtype=out_dtype)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[x_spec, hi_spec, scale_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((B, N), out_dtype),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
        )(x, hi, scale)

    k = lay.scheme.k
    if k > 1:
        lsb_spec = pl.BlockSpec((bk // (32 * k), bn), lambda b, n, kk: (kk, n))
    else:
        # dummy single-row plane, same block every step
        lsb_spec = pl.BlockSpec((1, bn), lambda b, n, kk: (0, n))
    kernel = functools.partial(
        _kernel_planes, lay=lay, bk=bk, bn=bn, nk=nk, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, hi_spec, lsb_spec, scale_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), out_dtype),
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(x, hi, lsb, scale)
