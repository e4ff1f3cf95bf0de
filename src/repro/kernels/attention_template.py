"""One templated online-softmax attention kernel for every decode path.

Every decode-time attention in this repo — the paged AMS/bf16 kernel in
`cache/paged_attention.py`, the contiguous GQA cores and the absorbed-MLA
cores in `models/attention.py` — is the same loop: scale q, walk KV in
blocks, accumulate a running (m, l, acc) online softmax with the additive
-2e30 mask, normalize once at the end. This module is the single home of
that loop, parameterized on three hooks (the AttentionEngine
score-mod/online-rowscale design, SNIPPETS.md Snippet 2):

  (a) **K/V load hook** — how one KV block reaches VREGs:
      * bf16/f32 pages or contiguous cache rows, cast to f32;
      * packed-e2m2 AMS planes (hi nibbles / shared-LSB words / scales)
        restored to exact lattice values in-kernel (`restore_page`) —
        dequantized pages are NEVER materialized in HBM, which is where
        the paper's 2.8-3.2x decode win lives;
      * a single compressed stream whose VALUES are its first ``hd_v``
        columns (absorbed MLA: v = k[:, :r_kv], nothing extra loaded).
  (b) **score-mod hook** — the family mapping: GQA's head-group fold is
      done host-side (q reshaped to chunk-major rows per kv head, so the
      kernel body is family-blind), MLA supplies its effective-rank scale
      and the value-slice width.
  (c) **ragged rows** — a [B, c] chunk folds its c queries into the row
      dimension of one grid cell; per-query lengths ride SCALAR PREFETCH
      (`pltpu.PrefetchScalarGridSpec`) next to the (paged-only) block
      table and per-slot page count, so BlockSpec index_maps see them
      before the body runs.

Two lowering tiers share the math:

  * `flash_decode` / `flash_decode_chunk` — the plain-XLA reference
    bodies (moved verbatim from `models.attention`; still re-exported
    there). These are the serving default (`impl="ref"`) and the oracle
    every fused path is pinned against; they also carry the
    sequence-sharded collectives (pmax/psum over ``axis_name``) that the
    fused kernel does not support.
  * `fused_paged_attention` / `fused_contiguous_attention` — the Pallas
    template (`impl="pallas"`/`"pallas_interpret"`), one grid
    (B, kv_blocks) with the KV dimension innermost ("arbitrary"); each
    cell holds every local kv head of its block and keeps one (m, l, acc)
    per head in VMEM scratch across the KV dimension. The paged walk stops
    at each slot's length: past the slot's last valid page a step fetches
    no page and attends nothing (the contiguous walk covers every block).

`attend_contiguous` is the dispatch the models cores call: it routes to
the fused template when the impl asks for it, and raises when the case is
not fusable (mesh collectives, ring/sliding window, a head map that is not
group-major) rather than run the XLA path under a Pallas name. Contiguous block
sizes come from `kernels.tuning.plan_attention_tiles` — a persistent
per-(shape, family, scheme) autotune cache with a deterministic
VMEM-budgeted default; set ``REPRO_ATTN_MEASURE=1`` to pick the block by
wall-clock instead (never in CI).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import get_scheme
from repro.core.kv_quant import codes_from_planes, packed_head_dim
from repro.kernels.ams_matmul import decode_codes_to_f32
from repro.kernels.tuning import plan_attention_tiles

NEG_BIG = -2e30   # additive mask; exp(NEG_BIG - NEG_CLAMP) == 0 exactly
NEG_CLAMP = -1e30


# ---------------------------------------------------------------------------
# XLA reference bodies (the `impl="ref"` tier and the fused path's oracle)
# ---------------------------------------------------------------------------
def _cache_positions(S_loc: int, pos, shard, ring_window: int):
    """Global key position held by each local cache slot.

    Full cache: slot j on shard s holds position s*S_loc + j. Ring (sliding
    window) cache of width W: global slot g holds the largest p <= pos with
    p % W == g (older entries were overwritten).
    """
    g = shard * S_loc + jnp.arange(S_loc)
    if ring_window:
        return pos - ((pos - g) % ring_window)
    return g


def flash_decode(
    q: jnp.ndarray,            # [B, H, hd]
    k_cache: jnp.ndarray,      # [B, S_loc, kv, hd]
    v_cache: jnp.ndarray,      # [B, S_loc, kv, hd_v]
    pos: jnp.ndarray,          # int32 current length (num valid keys):
                               #   scalar (shared) or [B] (per-slot lengths)
    *,
    kv_map: np.ndarray,
    axis_name: Optional[str] = None,   # mesh axis the S dim is sharded over
    window: int = 0,
    ring: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, H, hd = q.shape
    S_loc = k_cache.shape[1]
    hd_v = v_cache.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    shard = jax.lax.axis_index(axis_name) if axis_name else 0
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    pos_b = pos[:, None] if per_slot else pos  # broadcasts against [S_loc]
    k_pos = _cache_positions(S_loc, pos_b - 1, shard, window if ring else 0)

    kv_n = k_cache.shape[2]
    grouped = (H % kv_n == 0) and np.array_equal(
        kv_map, np.arange(H) // (H // kv_n))
    qf = q * np.float32(scale).astype(q.dtype)
    if grouped:
        g = H // kv_n
        qg = qf.reshape(B, kv_n, g, hd)
        s = jnp.einsum("bngd,bknd->bngk", qg, k_cache,
                       preferred_element_type=jnp.float32).reshape(B, H, S_loc)
    else:
        kvm = jnp.asarray(kv_map)
        ke = k_cache[:, :, kvm, :]
        s = jnp.einsum("bhd,bkhd->bhk", qf, ke,
                       preferred_element_type=jnp.float32)
    valid = (k_pos >= 0) & (k_pos < pos_b)  # ring slots may map to pre-history
    if window > 0:
        valid = valid & (pos_b - 1 - k_pos < window)
    # [B, 1, S_loc] when per-slot, [1, 1, S_loc] when shared
    vmask = valid[:, None, :] if per_slot else valid[None, None, :]
    s = jnp.where(vmask, s, -jnp.inf)

    m = s.max(axis=-1)                                   # [B, H]
    if axis_name:
        m = jax.lax.pmax(m, axis_name)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(vmask, p, 0.0)
    l = p.sum(axis=-1)                                   # [B, H]
    if grouped:
        g = H // kv_n
        pg = p.reshape(B, kv_n, g, S_loc)
        o = jnp.einsum("bngk,bknd->bngd", pg.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32).reshape(B, H, hd_v)
    else:
        ve = v_cache[:, :, kvm, :]
        o = jnp.einsum("bhk,bkhd->bhd", p.astype(ve.dtype), ve,
                       preferred_element_type=jnp.float32)
    if axis_name:
        l = jax.lax.psum(l, axis_name)
        o = jax.lax.psum(o, axis_name)
    return (o / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


def flash_decode_chunk(
    q: jnp.ndarray,            # [B, c, H, hd] query block (c <= chunk size)
    k_cache: jnp.ndarray,      # [B, S_loc, kv, hd]
    v_cache: jnp.ndarray,      # [B, S_loc, kv, hd_v]
    lengths: jnp.ndarray,      # [B, c] int32 valid keys PER QUERY (0 = masked
                               #   row -> exact-zero output)
    *,
    kv_map: np.ndarray,
    axis_name: Optional[str] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Chunked flash-decode: a [B, c] ragged query block attends the cache.

    Intra-chunk causality is carried entirely by ``lengths``: the caller
    inserts the chunk's keys FIRST, then sets query j's length to
    ``start + j + 1`` — so each query sees the prefix plus itself and the
    chunk entries before it, never the ones after. Rows past a slot's valid
    count get length 0 and flush to exact zeros (the engine discards them).
    Same additive-mask online-softmax math as `flash_decode`; no ring /
    sliding-window support (chunked mode is gated to plain-GQA / MLA
    families).
    """
    B, c, H, hd = q.shape
    S_loc = k_cache.shape[1]
    hd_v = v_cache.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    shard = jax.lax.axis_index(axis_name) if axis_name else 0
    lengths = jnp.asarray(lengths, jnp.int32)
    k_pos = shard * S_loc + jnp.arange(S_loc)        # [S_loc] global positions

    kv_n = k_cache.shape[2]
    grouped = (H % kv_n == 0) and np.array_equal(
        kv_map, np.arange(H) // (H // kv_n))
    qf = q * np.float32(scale).astype(q.dtype)
    if grouped:
        g = H // kv_n
        qg = qf.reshape(B, c, kv_n, g, hd)
        s = jnp.einsum("bcngd,bknd->bcngk", qg, k_cache,
                       preferred_element_type=jnp.float32)
        s = s.reshape(B, c, H, S_loc)
    else:
        kvm = jnp.asarray(kv_map)
        ke = k_cache[:, :, kvm, :]
        s = jnp.einsum("bchd,bkhd->bchk", qf, ke,
                       preferred_element_type=jnp.float32)
    valid = k_pos[None, None, :] < lengths[:, :, None]   # [B, c, S_loc]
    vmask = valid[:, :, None, :]                          # [B, c, 1, S_loc]
    s = jnp.where(vmask, s, -jnp.inf)

    m = s.max(axis=-1)                                    # [B, c, H]
    if axis_name:
        m = jax.lax.pmax(m, axis_name)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(vmask, p, 0.0)
    l = p.sum(axis=-1)                                    # [B, c, H]
    if grouped:
        g = H // kv_n
        pg = p.reshape(B, c, kv_n, g, S_loc)
        o = jnp.einsum("bcngk,bknd->bcngd", pg.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        o = o.reshape(B, c, H, hd_v)
    else:
        ve = v_cache[:, :, kvm, :]
        o = jnp.einsum("bchk,bkhd->bchd", p.astype(ve.dtype), ve,
                       preferred_element_type=jnp.float32)
    if axis_name:
        l = jax.lax.psum(l, axis_name)
        o = jax.lax.psum(o, axis_name)
    return (o / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# In-kernel pieces (shared by every fused layout)
# ---------------------------------------------------------------------------
def restore_page(hi, lsb, scale, fmt, k: int, hd: int) -> jnp.ndarray:
    """AMS load hook: packed planes of one (block, kv-head) cell ->
    [block, hd] f32 lattice values, restored in VREGs with the same
    SHIFT/AND/OR sequence as the weight kernel. hi: [block, hd_p//2] int8,
    lsb: [block, gw] int32, scale: [block, 1] f32."""
    codes = codes_from_planes(hi, lsb, k)
    vals = decode_codes_to_f32(codes, fmt) * scale
    return vals[:, :hd]


def row_lengths(len_ref, b, c: int, g: int):
    """Per-ROW valid-key counts [c*g, 1] for a chunked query block: the
    flattened lengths ride scalar prefetch as [B*c]; row r of the (c, g)-
    folded query block belongs to query r // g. c and g are static, so the
    gather is c scalar SMEM reads, each selected into its g rows (a vector
    compare per query: Mosaic lowers no gather, scatter or 0-sized slice)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c * g, 1), 0)
    out = jnp.zeros((c * g, 1), jnp.int32)
    for j in range(c):
        out = jnp.where(row // g == j, len_ref[b * c + j], out)
    return out


def softmax_init(acc_ref, m_ref, l_ref):
    """Reset one kv head's running (m, l, acc) before its first KV block."""
    m_ref[...] = jnp.full_like(m_ref, NEG_CLAMP)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def online_softmax_step(qf, k_blk, v_blk, length, i, acc_ref, m_ref, l_ref,
                        *, pv_dtype=jnp.float32):
    """One KV block of flash-decode accumulation for ONE kv head — THE loop
    body every fused layout shares. qf [rows, hd] f32 (pre-scaled; rows =
    chunk*group for ragged blocks), k_blk [block, hd] / v_blk [block, hd_v]
    f32, ``length`` a scalar or per-row [rows, 1] valid-key count, ``i``
    the block's index along the KV walk; the refs are that head's views of
    the scratch. A block whose keys are all masked leaves (m, l, acc)
    exactly as they were (p == 0, corr == 1), which is what lets the paged
    walk skip it. ``pv_dtype`` mirrors flash_decode's ``p.astype(v.dtype)``
    before the PV product (bf16 caches cast, AMS lattice values stay f32)
    so the oracle and the kernel round alike."""
    block = k_blk.shape[0]
    s = jax.lax.dot_general(qf, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [rows, block]
    k_pos = i * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    s = s + jnp.where(k_pos < length, 0.0, NEG_BIG)

    m_prev = m_ref[:, :1]                                  # [rows, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(jnp.maximum(m_prev, s.max(axis=-1, keepdims=True)),
                        NEG_CLAMP)
    p = jnp.exp(s - m_new)                                 # masked -> exact 0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(pv_dtype), v_blk.astype(pv_dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def softmax_finish(o_ref, acc_ref, l_ref):
    """Normalize one kv head's accumulator into its output block; a row
    that attended no key (l == 0, acc == 0) writes exact zeros."""
    l = l_ref[:, :1]
    out = acc_ref[...] / jnp.maximum(l, 1e-20)
    o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


# --- K/V load hooks -------------------------------------------------------
# Every KV block holds ALL local kv heads: a [1, block, kv, last] window of
# a [.., block-axis, kv, last] operand, so the block's last two dims equal
# the array's (the only legal TPU block for a head axis that sits
# second-to-last with fewer than 8 heads). A hook reads head ``h`` with a
# static second-minor index.
def _load_pair(kv_refs, h: int):
    """Separate K and V tensors (bf16/f32 pages or contiguous rows)."""
    k_ref, v_ref = kv_refs
    return (k_ref[0, :, h, :].astype(jnp.float32),
            v_ref[0, :, h, :].astype(jnp.float32))


def _make_load_stream(hd_v: int):
    """One compressed stream; values are its first hd_v columns (absorbed
    MLA) — V costs zero extra HBM reads."""
    def load(kv_refs, h: int):
        (k_ref,) = kv_refs
        k = k_ref[0, :, h, :].astype(jnp.float32)
        return k, k[:, :hd_v]
    return load


def _make_load_ams(fmt, k_share: int, hd: int, hd_v: Optional[int]):
    """AMS packed planes; with ``hd_v`` set, a single quantized stream whose
    values are the first hd_v restored columns."""
    def restore(hi, lsb, sc, h):
        return restore_page(hi[0, :, h, :], lsb[0, :, h, :], sc[0, :, h, :],
                            fmt, k_share, hd)

    def load(kv_refs, h: int):
        if hd_v is None:
            khi, klsb, ksc, vhi, vlsb, vsc = kv_refs
            return restore(khi, klsb, ksc, h), restore(vhi, vlsb, vsc, h)
        k = restore(*kv_refs, h)
        return k, k[:, :hd_v]
    return load


def _make_body(*, load_kv, nb: int, chunk: int, g: int, kv_n: int, pv_dtype,
               num_scalars: int, bounded: bool = False):
    """Assemble one kernel body from a load hook: one grid cell walks one
    KV block for every local kv head in turn. Ref order is fixed by the
    grid spec: [scalar prefetch...(lengths last), q, *kv operands, out,
    acc, m, l]. With ``bounded`` the scalar stream before the lengths is
    the per-slot block count [B]: a cell at or past it loads and attends
    nothing (every key there is masked, so skipping it changes no bit),
    while the init at the first cell and the normalize-and-store at the
    last still run, so an idle slot (count 0) writes exact zeros."""
    def body(*refs):
        len_ref = refs[num_scalars - 1]
        q_ref = refs[num_scalars]
        kv_refs = refs[num_scalars + 1:-4]
        o_ref, acc_ref, m_ref, l_ref = refs[-4:]
        b, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            for h in range(kv_n):
                softmax_init(acc_ref.at[h], m_ref.at[h], l_ref.at[h])

        def attend():
            lengths = row_lengths(len_ref, b, chunk, g)
            for h in range(kv_n):
                k_blk, v_blk = load_kv(kv_refs, h)
                online_softmax_step(q_ref[0, h].astype(jnp.float32), k_blk,
                                    v_blk, lengths, i, acc_ref.at[h],
                                    m_ref.at[h], l_ref.at[h],
                                    pv_dtype=pv_dtype)

        if bounded:                      # the page count precedes lengths
            pl.when(i < refs[num_scalars - 2][b])(attend)
        else:
            attend()

        @pl.when(i == nb - 1)
        def _done():
            for h in range(kv_n):
                softmax_finish(o_ref.at[0, h], acc_ref.at[h], l_ref.at[h])
    return body


# --- host-side fold / launch ----------------------------------------------
def _fold_q(q, lengths, kv_n: int, scale):
    """Scale q in q.dtype (the exact rounding flash_decode applies), fold
    the GQA groups chunk-major into the row dim ([B, kv, c*g, hd]), and
    flatten lengths to the [B*c] scalar-prefetch stream."""
    chunked = q.ndim == 4
    if not chunked:
        q = q[:, None]
        lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                                   (q.shape[0],))[:, None]
    B, c, H, hd = q.shape
    if H % kv_n != 0:
        raise ValueError(f"H={H} not grouped over kv={kv_n}")
    g = H // kv_n
    rows = c * g
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    qf = (q * np.float32(scale).astype(q.dtype)).astype(jnp.float32)
    # [B, c, kv, g, hd] -> [B, kv, c, g, hd]: chunk-major rows per kv head
    qf = qf.reshape(B, c, kv_n, g, hd).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(B, kv_n, rows, hd)
    lens = jnp.asarray(lengths, jnp.int32).reshape(-1)        # [B*c]
    return qf, lens, chunked, (B, c, H, hd, g, rows)


def _unfold_o(o, dims, hd_v: int, chunked: bool, dtype):
    B, c, H, hd, g, rows = dims
    kv_n = H // g
    o = o.reshape(B, kv_n, c, g, hd_v).transpose(0, 2, 1, 3, 4)
    o = o.reshape(B, c, H, hd_v).astype(dtype)
    return o if chunked else o[:, 0]


def _launch(body, grid, num_scalars, in_specs, out_spec, scalar_args,
            operands, *, kv_n, rows, hd_v, interpret):
    scratch = [pltpu.VMEM((kv_n, rows, hd_v), jnp.float32),   # acc
               pltpu.VMEM((kv_n, rows, 128), jnp.float32),    # m (col 0 live)
               pltpu.VMEM((kv_n, rows, 128), jnp.float32)]    # l (col 0 live)
    B, _ = grid
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalars, grid=grid,
        in_specs=in_specs, out_specs=out_spec, scratch_shapes=scratch)
    return pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv_n, rows, hd_v), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalar_args, *operands)


# ---------------------------------------------------------------------------
# Fused entry: paged pools (block table on scalar prefetch)
# ---------------------------------------------------------------------------
def fused_paged_attention(
    q: jnp.ndarray,              # [B, H, hd] or [B, c, H, hd] UNSCALED
    pool,                        # layer pool (cache.pool layout)
    lengths: jnp.ndarray,        # [B] int32 valid keys (<=0: idle slot);
                                 #   [B, c] per-query for chunked q
    block_table: jnp.ndarray,    # [B, max_pages_per_seq] int32
    *,
    page_size: int,
    kv_scheme: Optional[str] = None,   # AMS scheme name; None = bf16 pages
    value_slice: Optional[int] = None,  # MLA: v = k[:, :value_slice]
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged flash-decode through the template. Requires the group-major
    GQA head layout (the only layout the model zoo emits — see
    `kv_index_map`); returns q's shape in q.dtype. One grid step attends
    one (slot, page) cell for every local kv head; the block table, the
    per-slot page count and the flattened per-query lengths ride the same
    scalar-prefetch stream, so each page's BlockSpec index_map dereferences
    ``block_table[b, i]`` BEFORE the body runs.

    The walk stops at each slot's length: the page count is
    ``ceil(max_j lengths[b, j] / page_size)`` (the longest query row, since
    a ragged chunk's trailing rows are 0; an idle slot counts 0). Steps
    past it keep the last valid page's block index, so the pipeline issues
    no DMA for them whatever the row's tail holds, and skip the load and
    the accumulate. Those pages' keys are all masked, so the output is the
    same, bit for bit, as attending them; a slot at full length does the
    whole row's work."""
    kv_n = jax.tree.leaves(pool["k"])[0].shape[2]
    qf, lens, chunked, dims = _fold_q(q, lengths, kv_n, scale)
    B, c, H, hd, g, rows = dims
    hd_v = hd if value_slice is None else value_slice
    page = page_size
    nb = block_table.shape[1]
    bt_flat = block_table.reshape(-1).astype(jnp.int32)
    top = lens.reshape(B, c).max(axis=1)            # longest query row
    npg = jnp.clip((top + page - 1) // page, 0, nb).astype(jnp.int32)

    # index maps: scalar-prefetch refs arrive after the grid indices
    q_spec = pl.BlockSpec((1, kv_n, rows, hd),
                          lambda b, i, bt, n, ln: (b, 0, 0, 0))
    out_spec = pl.BlockSpec((1, kv_n, rows, hd_v),
                            lambda b, i, bt, n, ln: (b, 0, 0, 0))

    def page_index(b, i, bt, n, ln):
        final = jnp.maximum(n[b] - 1, 0)
        return (bt[b * nb + jnp.minimum(i, final)], 0, 0, 0)

    def page_spec(last):
        return pl.BlockSpec((1, page, kv_n, last), page_index)

    if kv_scheme is not None:
        scheme = get_scheme(kv_scheme)
        hd_p = packed_head_dim(hd, scheme)
        gw = pool["k"]["lsb"].shape[-1]
        load = _make_load_ams(scheme.base, scheme.k, hd, value_slice)
        plane_specs = [page_spec(hd_p // 2), page_spec(gw), page_spec(1)]
        operands = [qf, pool["k"]["hi"], pool["k"]["lsb"], pool["k"]["scale"]]
        in_specs = [q_spec] + plane_specs
        if value_slice is None:
            operands += [pool["v"]["hi"], pool["v"]["lsb"], pool["v"]["scale"]]
            in_specs += plane_specs
        pv_dtype = jnp.float32
    else:
        if value_slice is None:
            load = _load_pair
            in_specs = [q_spec, page_spec(hd), page_spec(hd)]
            operands = [qf, pool["k"], pool["v"]]
        else:
            load = _make_load_stream(value_slice)
            in_specs = [q_spec, page_spec(hd)]
            operands = [qf, pool["k"]]
        pv_dtype = jax.tree.leaves(pool["k"])[0].dtype

    body = _make_body(load_kv=load, nb=nb, chunk=c, g=g, kv_n=kv_n,
                      pv_dtype=pv_dtype, num_scalars=3, bounded=True)
    o = _launch(body, (B, nb), 3, in_specs, out_spec,
                (bt_flat, npg, lens), operands, kv_n=kv_n, rows=rows,
                hd_v=hd_v, interpret=interpret)
    return _unfold_o(o, dims, hd_v, chunked, q.dtype)


# ---------------------------------------------------------------------------
# Fused entry: contiguous caches (autotuned KV block)
# ---------------------------------------------------------------------------
def fused_contiguous_attention(
    q: jnp.ndarray,              # [B, H, hd] or [B, c, H, hd] UNSCALED
    k_cache: jnp.ndarray,        # [B, S_loc, kv, hd]
    lengths: jnp.ndarray,        # [B] or [B, c] int32 valid keys
    *,
    v_cache: Optional[jnp.ndarray] = None,   # [B, S_loc, kv, hd]; None with
    value_slice: Optional[int] = None,       #   value_slice (MLA stream)
    block_kv: Optional[int] = None,          # override the autotune plan
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Contiguous-cache flash-decode through the same template: grid
    (B, S_loc/block_kv), cache rows DMA'd block-by-block, lengths
    on scalar prefetch. ``block_kv`` comes from the per-(shape, family)
    autotune cache (`kernels.tuning.plan_attention_tiles`) unless
    overridden; candidates are divisors of S_loc so no block ever reads
    past the cache."""
    kv_n = k_cache.shape[2]
    S_loc = k_cache.shape[1]
    qf, lens, chunked, dims = _fold_q(q, lengths, kv_n, scale)
    B, c, H, hd, g, rows = dims
    hd_v = hd if value_slice is None else value_slice
    if value_slice is None and v_cache is None:
        raise ValueError("need v_cache or value_slice")

    if value_slice is None:
        load = _load_pair
        n_kv = 2
    else:
        load = _make_load_stream(value_slice)
        n_kv = 1
    pv_dtype = (v_cache if v_cache is not None else k_cache).dtype

    def run(bk: int):
        nb = S_loc // bk
        q_spec = pl.BlockSpec((1, kv_n, rows, hd), lambda b, i, ln: (b, 0, 0, 0))
        out_spec = pl.BlockSpec((1, kv_n, rows, hd_v),
                                lambda b, i, ln: (b, 0, 0, 0))
        kv_spec = pl.BlockSpec((1, bk, kv_n, hd), lambda b, i, ln: (b, i, 0, 0))
        in_specs = [q_spec] + [kv_spec] * n_kv
        operands = ([qf, k_cache, v_cache] if value_slice is None
                    else [qf, k_cache])
        body = _make_body(load_kv=load, nb=nb, chunk=c, g=g, kv_n=kv_n,
                          pv_dtype=pv_dtype, num_scalars=1)
        o = _launch(body, (B, nb), 1, in_specs, out_spec, (lens,),
                    operands, kv_n=kv_n, rows=rows, hd_v=hd_v,
                    interpret=interpret)
        return o

    if block_kv is None:
        family = "mla" if value_slice is not None else "gqa"
        measure = None
        if os.environ.get("REPRO_ATTN_MEASURE") == "1":
            import time

            def measure(plan):
                jax.block_until_ready(run(plan.block_kv))      # compile+warm
                t0 = time.perf_counter()
                jax.block_until_ready(run(plan.block_kv))
                return time.perf_counter() - t0
        # kv_n is the LOCAL head count of the operand (a per-shard slice
        # under tensor parallelism) — it keys the plan so a tp=1 tuning is
        # never silently reused for a different grid height
        plan = plan_attention_tiles(
            kind="contiguous", family=family, scheme=None, rows=rows,
            hd=hd, hd_v=hd_v, s_max=S_loc, kv_heads=kv_n, measure=measure)
        block_kv = plan.block_kv
    if S_loc % block_kv != 0:
        raise ValueError(f"block_kv={block_kv} must divide S_loc={S_loc}")
    o = run(block_kv)
    return _unfold_o(o, dims, hd_v, chunked, q.dtype)


# ---------------------------------------------------------------------------
# Dispatch: the single entry the models cores call
# ---------------------------------------------------------------------------
def attend_contiguous(
    q: jnp.ndarray,              # [B, H, hd] (one-token) or [B, c, H, hd]
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,        # ref-path values (MLA: the [..., :r_kv] view)
    lengths: jnp.ndarray,        # one-token: pos+1 (scalar or [B]);
                                 #   chunked: [B, c] per-query lengths
    *,
    kv_map: np.ndarray,
    scale: Optional[float] = None,
    impl: str = "ref",
    axis_name: Optional[str] = None,
    window: int = 0,
    ring: bool = False,
    value_slice: Optional[int] = None,   # MLA: fuse v = k_cache[..., :r_kv]
) -> jnp.ndarray:
    """Decode attention over a contiguous cache, routed by ``impl``.

    ``impl="ref"`` (the serving default) IS `flash_decode` /
    `flash_decode_chunk` — bit-identical to the pre-template cores.
    ``impl="pallas"``/``"pallas_interpret"`` lowers through the fused
    template; a case the template cannot take (sequence-sharded cores
    with ``axis_name``, ring / sliding-window caches, non-group-major head
    maps — the collectives and ring index math live only in the XLA path)
    raises instead of silently running XLA, as `cache.paged_attend`
    does."""
    if impl in ("pallas", "pallas_interpret"):
        H, kv_n = q.shape[-2], k_cache.shape[2]
        grouped = (H % kv_n == 0) and np.array_equal(
            np.asarray(kv_map), np.arange(H) // (H // kv_n))
        if axis_name is not None or window or ring or not grouped:
            raise NotImplementedError(
                f"impl={impl!r} fused attention needs a group-major head map "
                f"and an unsharded, non-windowed cache (axis_name="
                f"{axis_name!r}, window={window}, ring={ring}, "
                f"group-major={grouped}); use impl='ref'")
        return fused_contiguous_attention(
            q, k_cache, lengths,
            v_cache=None if value_slice is not None else v_cache,
            value_slice=value_slice, scale=scale,
            interpret=(impl == "pallas_interpret"))
    if q.ndim == 3:
        return flash_decode(q, k_cache, v_cache, lengths, kv_map=kv_map,
                            axis_name=axis_name, window=window, ring=ring,
                            scale=scale)
    return flash_decode_chunk(q, k_cache, v_cache, lengths, kv_map=kv_map,
                              axis_name=axis_name, scale=scale)
