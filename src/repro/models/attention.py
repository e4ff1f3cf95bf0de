"""Attention: GQA / MLA / sliding-window, blockwise prefill + flash decode.

Memory-safe by construction:
  * train/prefill use a blockwise online-softmax scan over KV blocks
    (O(S * block) live memory — a 32k prefill never materializes S x S);
  * decode uses flash-decoding: when the KV cache is sequence-sharded over
    the `model` mesh axis (our layout for 32k+ caches), each shard computes a
    partial attention and a log-sum-exp, merged with 3 small collectives.

MLA (MiniCPM3/DeepSeek-style) runs in the *absorbed* form everywhere: scores
and values are computed directly against the compressed KV stream
(kv_lora + rope dims), which is what makes its decode cache tiny.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The decode online-softmax bodies live in the fused attention template
# (`repro.kernels.attention_template`) — `flash_decode`/`flash_decode_chunk`
# are re-exported here for their historical import path, and every decode
# core below routes through `attend_contiguous` (impl="ref" IS those
# functions, bit-identical; "pallas"/"pallas_interpret" lowers the same
# math through the fused Pallas kernel).
from repro.kernels.attention_template import (  # noqa: F401
    _cache_positions,
    attend_contiguous,
    flash_decode,
    flash_decode_chunk,
)

from .common import apply_linear, apply_rope, make_linear, model_dims


def kv_index_map(H_pad: int, H_true: int, kv: int) -> np.ndarray:
    """Static map q-head slot -> kv head under the group-major layout
    (see Dims): slot j attends kv head j // (H_pad // kv). Uniform by
    construction, so attention always takes the grouped-einsum path."""
    assert H_pad % kv == 0
    return (np.arange(H_pad) // (H_pad // kv)).astype(np.int32)


# ---------------------------------------------------------------------------
# Blockwise attention (train / prefill)
# ---------------------------------------------------------------------------
def blockwise_attention(
    q: jnp.ndarray,            # [B, Sq, H, hd]
    k: jnp.ndarray,            # [B, Skv, kv, hd]
    v: jnp.ndarray,            # [B, Skv, kv, hd_v]
    *,
    kv_map: np.ndarray,        # [H] -> kv head
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,       # bidirectional prefix (VLM patches)
    block_kv: int = 1024,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    hd_v = v.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    bkv = min(block_kv, Skv)
    nb = -(-Skv // bkv)
    Skp = nb * bkv

    kp = jnp.pad(k, ((0, 0), (0, Skp - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Skp - Skv), (0, 0), (0, 0)))
    kp = kp.reshape(B, nb, bkv, k.shape[2], hd).transpose(1, 0, 2, 3, 4)
    vp = vp.reshape(B, nb, bkv, v.shape[2], hd_v).transpose(1, 0, 2, 3, 4)

    qf = q * np.float32(scale).astype(q.dtype)
    q_pos = q_offset + jnp.arange(Sq)
    kvm = jnp.asarray(kv_map)
    k_pos_blocks = jnp.arange(Skp, dtype=jnp.int32).reshape(nb, bkv)

    kv_n = k.shape[2]
    grouped = (H % kv_n == 0) and np.array_equal(
        kv_map, np.arange(H) // (H // kv_n))

    def body(carry, blk):
        m, l, acc = carry
        kj, vj, k_pos = blk  # [B, bkv, kv, hd], ..., [bkv]
        if grouped:
            # grouped einsum: no H-fold materialization of K/V
            g = H // kv_n
            qg = qf.reshape(B, Sq, kv_n, g, hd)
            s = jnp.einsum("bqngd,bknd->bngqk", qg, kj,
                           preferred_element_type=jnp.float32)
            s = s.reshape(B, H, Sq, s.shape[-1])
        else:
            kje = kj[:, :, kvm, :]      # [B, bkv, H, hd]
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kje,
                           preferred_element_type=jnp.float32)
        valid = (k_pos < Skv)[None, :]
        if causal:
            vis = k_pos[None, :] <= q_pos[:, None]
            if prefix_len > 0:
                vis = vis | (k_pos[None, :] < prefix_len)
            valid = valid & vis
        if window > 0:
            valid = valid & (q_pos[:, None] - k_pos[None, :] < window)
        # additive mask bias instead of two where() passes over the score
        # tensor: masked entries sit at -2e30; the running max is clamped to
        # -1e30, so exp(masked - max) == exp(-1e30) underflows to exactly 0
        # and rows with no valid key yet keep l == 0. No post-exp select,
        # no +/-inf arithmetic -> fewer full-score HBM round trips.
        s = s + jnp.where(valid, 0.0, -2e30)[None, None]

        m_new = jnp.maximum(jnp.maximum(m, s.max(axis=-1)), -1e30)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        if grouped:
            g = H // kv_n
            pg = p.reshape(B, kv_n, g, Sq, p.shape[-1])
            pv = jnp.einsum("bngqk,bknd->bngqd", pg.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            pv = pv.reshape(B, H, Sq, hd_v)
        else:
            vje = vj[:, :, kvm, :]
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vj.dtype), vje,
                            preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd_v), jnp.float32)
    # flash-attention residency: recompute scores per block in the backward
    # instead of letting scan stack [n_blocks, B, H, Sq, bkv] f32 residuals
    # (measured: the stacked scores dominated train-step HBM traffic).
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (kp, vp, k_pos_blocks))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Sq, H, hd_v]


def cache_insert(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray,
                 axis_name: Optional[str] = None, ring_window: int = 0) -> jnp.ndarray:
    """Insert `new` [B, 1, kv, hd] at global position `pos` into a (possibly
    sequence-sharded, possibly ring) cache [B, S_loc, kv, hd]; no-op on
    non-owner shards.

    `pos` is a scalar (whole batch at one position — the one-shot decode
    loop) or [B] per-slot positions (continuous-batching engine). A negative
    per-slot position suppresses the write entirely (idle slot)."""
    S_loc = cache.shape[1]
    shard = jax.lax.axis_index(axis_name) if axis_name else 0
    pos = jnp.asarray(pos, jnp.int32)

    def insert_one(c, n, p, seq_axis):
        slot = (p % ring_window) if ring_window else p
        local = slot - shard * S_loc
        in_range = (p >= 0) & (local >= 0) & (local < S_loc)
        idx = jnp.clip(local, 0, S_loc - 1)
        # select on the 1-token slice, NOT the whole cache (keeps the update
        # O(new) in HBM traffic; a full-cache where() costs a cache-sized
        # select per layer per step)
        old = jax.lax.dynamic_slice_in_dim(c, idx, 1, axis=seq_axis)
        val = jnp.where(in_range, n.astype(c.dtype), old)
        return jax.lax.dynamic_update_slice_in_dim(c, val, idx, axis=seq_axis)

    if pos.ndim == 1:  # per-slot: vmap over the batch dim
        return jax.vmap(lambda c, n, p: insert_one(c, n, p, 0))(cache, new, pos)
    return insert_one(cache, new, pos, 1)


def cache_insert_chunk(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray,
                       nvalid: jnp.ndarray,
                       axis_name: Optional[str] = None) -> jnp.ndarray:
    """Insert a ragged chunk `new` [B, c, kv, hd] at per-slot start positions
    ``pos`` [B] into a (possibly sequence-sharded) cache [B, S_loc, kv, hd].

    Slot b writes positions ``pos[b] .. pos[b] + nvalid[b] - 1``; entries at
    chunk index >= nvalid[b] (and whole slots with pos < 0) are routed to an
    out-of-range row index and dropped by the scatter — one scatter per
    layer, no full-cache select.
    """
    B, c = new.shape[0], new.shape[1]
    S_loc = cache.shape[1]
    shard = jax.lax.axis_index(axis_name) if axis_name else 0
    pos = jnp.asarray(pos, jnp.int32)
    nvalid = jnp.asarray(nvalid, jnp.int32)
    j = jnp.arange(c, dtype=jnp.int32)[None, :]
    p = pos[:, None] + j                               # [B, c] global positions
    local = p - shard * S_loc
    ok = ((pos[:, None] >= 0) & (j < nvalid[:, None])
          & (local >= 0) & (local < S_loc))
    idx = jnp.where(ok, local, S_loc)                  # OOB -> dropped
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    return cache.at[b_idx, idx].set(new.astype(cache.dtype), mode="drop")


def cache_truncate_chunk(cache: jnp.ndarray, start: jnp.ndarray,
                         count: jnp.ndarray, c_max: int,
                         axis_name: Optional[str] = None) -> jnp.ndarray:
    """Zero per-slot positions ``start[b] .. start[b] + count[b] - 1`` of a
    contiguous cache leaf [B, S_loc, ...] — the inverse of
    `cache_insert_chunk`, restoring the zero-initialized state so a later
    re-insert is bit-identical to a straight insert. Used by the
    speculative engine step to un-insert rejected draft tokens; slots with
    ``count == 0`` (or ``start < 0``) are no-ops via the same
    out-of-range-row drop the insert uses. ``c_max`` is the static rewind
    width bound."""
    B, S_loc = cache.shape[0], cache.shape[1]
    shard = jax.lax.axis_index(axis_name) if axis_name else 0
    start = jnp.asarray(start, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    j = jnp.arange(c_max, dtype=jnp.int32)[None, :]
    local = start[:, None] + j - shard * S_loc
    ok = ((start[:, None] >= 0) & (j < count[:, None])
          & (local >= 0) & (local < S_loc))
    idx = jnp.where(ok, local, S_loc)                  # OOB -> dropped
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    return cache.at[b_idx, idx].set(jnp.zeros((), cache.dtype), mode="drop")


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------
def init_gqa(key, cfg, dims, dtype=jnp.float32):
    D, hd = cfg.d_model, dims.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": make_linear(ks[0], D, dims.H * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": make_linear(ks[1], D, dims.kv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": make_linear(ks[2], D, dims.kv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": make_linear(ks[3], dims.H * hd, D, dtype=dtype),
    }


def gqa_qkv(p, x, cfg, dims, positions, policy=None):
    """Project + rope. x: [B, S, D] -> q [B,S,H,hd], k/v [B,S,kv,hd]."""
    B, S, _ = x.shape
    hd = dims.hd
    q = apply_linear(p["wq"], x, policy).reshape(B, S, dims.H, hd)
    k = apply_linear(p["wk"], x, policy).reshape(B, S, dims.kv, hd)
    v = apply_linear(p["wv"], x, policy).reshape(B, S, dims.kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attn_train(p, x, cfg, dims, *, policy=None, block_kv=1024,
                   prefix_len=0, window=0):
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = gqa_qkv(p, x, cfg, dims, positions, policy)
    kvm = kv_index_map(dims.H, dims.H_true, dims.kv)
    o = blockwise_attention(q, k, v, kv_map=kvm, causal=True,
                            window=window or cfg.sliding_window,
                            prefix_len=prefix_len, block_kv=block_kv)
    o = o * dims.head_mask[None, None, :, None].astype(o.dtype)
    o = o.reshape(B, S, dims.H * dims.hd)
    return apply_linear(p["wo"], o, policy), (k, v)


def gqa_decode_core(q, k_new, v_new, cache_k, cache_v, pos, *,
                    kv_map, window=0, ring=False, scale=None, axis_name=None,
                    impl="ref"):
    """Insert + attend. q: [B, H, hd]; k/v_new: [B, 1, kv, hd];
    caches [B, S_loc, kv, hd]. Runs inside shard_map when the cache is
    sequence-sharded over `axis_name` (only ``impl="ref"`` carries the
    collectives; a fused impl raises there)."""
    cache_k = cache_insert(cache_k, k_new, pos, axis_name, window if ring else 0)
    cache_v = cache_insert(cache_v, v_new, pos, axis_name, window if ring else 0)
    o = attend_contiguous(q, cache_k, cache_v, pos + 1, kv_map=kv_map,
                          axis_name=axis_name, window=window, ring=ring,
                          scale=scale, impl=impl)
    return o, cache_k, cache_v


def gqa_paged_core(q, k_new, v_new, pool, pos, block_tables, *, cache_cfg,
                   scale=None):
    """Paged insert + attend core. q: [B, H, hd]; k/v_new: [B, 1, kv, hd];
    ``pool`` is one layer's page pool. The kv_map is recomputed from the
    OPERAND shapes, not the global dims: under the head-sharded shard_map
    wrap (transformer.py) this core sees each device's local head slice,
    and the group-major layout keeps the local map the same
    ``arange(H) // (H // kv)`` formula at local counts — so quantize,
    scatter-insert and attend all run device-local, and no page ever
    crosses the mesh."""
    from repro.cache import paged_attend, paged_insert

    pool = paged_insert(pool, k_new, v_new, pos, block_tables, cache_cfg)
    kvm = kv_index_map(q.shape[-2], q.shape[-2], k_new.shape[-2])
    lengths = jnp.where(pos >= 0, pos + 1, 0)
    o = paged_attend(q, pool, lengths, block_tables, cache_cfg,
                     kv_map=kvm, scale=scale)
    return o, pool


def gqa_attn_decode_paged(p, x, pool, pos, block_tables, cfg, dims, *,
                          policy=None, cache_cfg=None, core_wrap=None):
    """Paged-cache decode step: x [B, 1, D]; ``pool`` is one layer's page
    pool (repro.cache.pool layout); ``block_tables`` [B, max_pages] int32.

    Each slot's new K/V vector is quantized ONCE at insert (paged-AMS) or
    stored bf16 (paged-bf16); attention walks the block table via the
    configured impl (``ref`` gather-dequantize oracle or the Pallas
    kernel). ``core_wrap(core_fn)`` lets the caller shard_map the
    insert+attend core over local kv-head slices (transformer.py passes a
    wrapper when the pool is head-sharded over the model axis). Returns
    (out, new pool)."""
    import functools
    B = x.shape[0]
    hd = dims.hd
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((B, 1), pos,
                                                            jnp.int32)
    q, k, v = gqa_qkv(p, x, cfg, dims, positions, policy)
    core = functools.partial(gqa_paged_core, cache_cfg=cache_cfg)
    if core_wrap is not None:
        core = core_wrap(core)
    o, pool = core(q[:, 0], k, v, pool, pos, block_tables)
    o = o * dims.head_mask[None, :, None].astype(o.dtype)
    o = o.reshape(B, 1, dims.H * hd)
    return apply_linear(p["wo"], o, policy), pool


def gqa_attn_decode(p, x, cache_k, cache_v, pos, cfg, dims, *,
                    policy=None, core_wrap=None, window=0, ring=False,
                    attn_impl="ref"):
    """x: [B, 1, D]; caches [B, S_loc, kv, hd]. Returns (out, new caches).

    ``core_wrap(core_fn)`` lets the caller shard_map the insert+attend core
    (transformer.py passes a wrapper when the cache is sequence-sharded).
    ``pos`` is scalar or [B] (per-slot continuous batching)."""
    import functools
    B = x.shape[0]
    hd = dims.hd
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((B, 1), pos,
                                                            jnp.int32)
    q, k, v = gqa_qkv(p, x, cfg, dims, positions, policy)
    kvm = kv_index_map(dims.H, dims.H_true, dims.kv)
    core = functools.partial(gqa_decode_core, kv_map=kvm,
                             window=window or cfg.sliding_window, ring=ring,
                             impl=attn_impl)
    if core_wrap is not None:
        core = core_wrap(core)
    o, cache_k, cache_v = core(q[:, 0], k, v, cache_k, cache_v, pos)
    o = o * dims.head_mask[None, :, None].astype(o.dtype)
    o = o.reshape(B, 1, dims.H * hd)
    return apply_linear(p["wo"], o, policy), (cache_k, cache_v)


# ---------------------------------------------------------------------------
# Ragged chunked decode (multi-token engine step)
# ---------------------------------------------------------------------------
def chunk_lengths(pos: jnp.ndarray, nvalid: jnp.ndarray, c: int) -> jnp.ndarray:
    """Per-query valid-key counts [B, c] for a chunk inserted at ``pos``:
    query j attends the prefix plus itself (pos + j + 1); rows past nvalid
    (or idle slots, pos < 0) get 0 and flush to exact zeros."""
    pos = jnp.asarray(pos, jnp.int32)
    nvalid = jnp.asarray(nvalid, jnp.int32)
    j = jnp.arange(c, dtype=jnp.int32)[None, :]
    ok = (pos[:, None] >= 0) & (j < nvalid[:, None])
    return jnp.where(ok, pos[:, None] + j + 1, 0)


def gqa_decode_core_chunk(q, k_new, v_new, cache_k, cache_v, pos, nvalid, *,
                          kv_map, scale=None, axis_name=None, impl="ref"):
    """Chunked insert + attend. q: [B, c, H, hd]; k/v_new: [B, c, kv, hd];
    caches [B, S_loc, kv, hd]; pos/nvalid [B]. Keys land first, then every
    query attends with its own length (intra-chunk causal by construction)."""
    cache_k = cache_insert_chunk(cache_k, k_new, pos, nvalid, axis_name)
    cache_v = cache_insert_chunk(cache_v, v_new, pos, nvalid, axis_name)
    lengths = chunk_lengths(pos, nvalid, q.shape[1])
    o = attend_contiguous(q, cache_k, cache_v, lengths, kv_map=kv_map,
                          axis_name=axis_name, scale=scale, impl=impl)
    return o, cache_k, cache_v


def gqa_attn_decode_chunk(p, x, cache_k, cache_v, pos, nvalid, cfg, dims, *,
                          policy=None, core_wrap=None, attn_impl="ref"):
    """Ragged multi-token decode: x [B, c, D], per-slot start positions
    ``pos`` [B] and valid counts ``nvalid`` [B]. Returns (out [B, c, D],
    new caches); rows past a slot's nvalid are exact no-ops."""
    import functools
    B, c, _ = x.shape
    hd = dims.hd
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.maximum(pos[:, None] + jnp.arange(c, dtype=jnp.int32), 0)
    q, k, v = gqa_qkv(p, x, cfg, dims, positions, policy)
    kvm = kv_index_map(dims.H, dims.H_true, dims.kv)
    core = functools.partial(gqa_decode_core_chunk, kv_map=kvm,
                             impl=attn_impl)
    if core_wrap is not None:
        core = core_wrap(core)
    o, cache_k, cache_v = core(q, k, v, cache_k, cache_v, pos, nvalid)
    o = o * dims.head_mask[None, None, :, None].astype(o.dtype)
    o = o.reshape(B, c, dims.H * hd)
    return apply_linear(p["wo"], o, policy), (cache_k, cache_v)


def gqa_paged_core_chunk(q, k_new, v_new, pool, pos, block_tables, nvalid, *,
                         cache_cfg, scale=None):
    """Chunked paged insert + attend core. q: [B, c, H, hd]; k/v_new
    [B, c, kv, hd]. Same local-shape kv_map discipline as
    `gqa_paged_core` — runs unchanged on a device-local head slice under
    the head-sharded shard_map wrap."""
    from repro.cache import paged_attend, paged_insert

    pool = paged_insert(pool, k_new, v_new, pos, block_tables, cache_cfg,
                        nvalid=nvalid)
    kvm = kv_index_map(q.shape[-2], q.shape[-2], k_new.shape[-2])
    lengths = chunk_lengths(pos, nvalid, q.shape[1])
    o = paged_attend(q, pool, lengths, block_tables, cache_cfg,
                     kv_map=kvm, scale=scale)
    return o, pool


def gqa_attn_decode_paged_chunk(p, x, pool, pos, nvalid, block_tables, cfg,
                                dims, *, policy=None, cache_cfg=None,
                                core_wrap=None):
    """Paged ragged decode: x [B, c, D]; the chunk's K/V vectors are packed
    into the layer pool in ONE multi-token scatter per plane
    (`cache.pool.paged_insert` with nvalid), then every query attends the
    block table with its own length through the configured impl.
    ``core_wrap`` as in `gqa_attn_decode_paged`."""
    import functools
    B, c, _ = x.shape
    hd = dims.hd
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.maximum(pos[:, None] + jnp.arange(c, dtype=jnp.int32), 0)
    q, k, v = gqa_qkv(p, x, cfg, dims, positions, policy)
    core = functools.partial(gqa_paged_core_chunk, cache_cfg=cache_cfg)
    if core_wrap is not None:
        core = core_wrap(core)
    o, pool = core(q, k, v, pool, pos, block_tables, nvalid)
    o = o * dims.head_mask[None, None, :, None].astype(o.dtype)
    o = o.reshape(B, c, dims.H * hd)
    return apply_linear(p["wo"], o, policy), pool


# ---------------------------------------------------------------------------
# MLA (absorbed form)
# ---------------------------------------------------------------------------
def init_mla(key, cfg, dims, dtype=jnp.float32):
    D = cfg.d_model
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    H = dims.H
    ks = jax.random.split(key, 7)
    return {
        "wq_a": make_linear(ks[0], D, r_q, dtype=dtype),
        "q_a_norm": jnp.ones((r_q,), dtype),
        "wq_b": make_linear(ks[1], r_q, H * (dn + dr), dtype=dtype),
        "wkv_a": make_linear(ks[2], D, r_kv + dr, dtype=dtype),
        "kv_a_norm": jnp.ones((r_kv,), dtype),
        # absorbed decompression factors, stored per head:
        "w_uk": make_linear(ks[3], r_kv, H * dn, dtype=dtype),   # key-nope
        "w_uv": make_linear(ks[4], r_kv, H * dv, dtype=dtype),   # value
        "wo": make_linear(ks[5], H * dv, D, dtype=dtype),
    }


def _mla_q_eff(p, x, cfg, dims, positions, policy):
    """Absorbed query: q_eff [B, S, H, r_kv + dr]."""
    from .common import rms_norm
    B, S, _ = x.shape
    H = dims.H
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    r_kv = cfg.kv_lora_rank
    cq = rms_norm(apply_linear(p["wq_a"], x, policy), p["q_a_norm"], cfg.norm_eps)
    q = apply_linear(p["wq_b"], cq, policy).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # absorb: q_nope^T (W_uk per head) -> compressed space
    from .common import materialize_weight
    w_uk = materialize_weight(p["w_uk"], r_kv, q_nope.dtype, policy).reshape(r_kv, H, dn)
    q_c = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk.astype(q_nope.dtype))
    return jnp.concatenate([q_c, q_rope], axis=-1)  # [B,S,H,r_kv+dr]


def _mla_kv_stream(p, x, cfg, positions, policy):
    """Compressed KV stream [B, S, r_kv + dr] (this is the decode cache)."""
    from .common import rms_norm
    dr = cfg.qk_rope_dim
    r_kv = cfg.kv_lora_rank
    ckv = apply_linear(p["wkv_a"], x, policy)
    c, k_rope = ckv[..., :r_kv], ckv[..., r_kv:]
    c = rms_norm(c, p["kv_a_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_rope], axis=-1)


def _mla_out(p, attn_c, cfg, dims, policy):
    """attn_c: [B, S, H, r_kv] attention-weighted compressed values."""
    B, S, H, r_kv = attn_c.shape
    dv = cfg.v_head_dim
    from .common import materialize_weight
    w_uv = materialize_weight(p["w_uv"], r_kv, attn_c.dtype, policy).reshape(r_kv, H, dv)
    o = jnp.einsum("bshr,rhd->bshd", attn_c, w_uv.astype(attn_c.dtype))
    o = o * dims.head_mask[None, None, :, None].astype(o.dtype)
    return apply_linear(p["wo"], o.reshape(B, S, H * dv), policy)


def mla_attn_train(p, x, cfg, dims, *, policy=None, block_kv=1024, prefix_len=0):
    B, S, _ = x.shape
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    positions = jnp.arange(S)[None, :]
    q_eff = _mla_q_eff(p, x, cfg, dims, positions, policy)
    kv = _mla_kv_stream(p, x, cfg, positions, policy)   # [B, S, r_kv+dr]
    # single shared "kv head" of width r_kv+dr; values = compressed stream r_kv
    k1 = kv[:, :, None, :]
    v1 = kv[:, :, None, :r_kv]
    kvm = np.zeros((dims.H,), np.int32)
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + dr)
    o_c = blockwise_attention(q_eff, k1, v1, kv_map=kvm, causal=True,
                              prefix_len=prefix_len, block_kv=block_kv,
                              scale=scale)
    out = _mla_out(p, o_c, cfg, dims, policy)
    return out, kv


def mla_decode_core(q_eff, kv_new, cache_kv, pos, *, r_kv, scale,
                    axis_name=None, impl="ref"):
    """cache_kv: [B, S_loc, 1, r_kv+dr]; kv_new: [B, 1, 1, r_kv+dr]. The
    fused path slices values from the SAME compressed stream in-kernel
    (``value_slice=r_kv``) — V costs no extra HBM reads."""
    H = q_eff.shape[1]
    cache_kv = cache_insert(cache_kv, kv_new, pos, axis_name)
    kvm = np.zeros((H,), np.int32)
    o_c = attend_contiguous(q_eff, cache_kv, cache_kv[..., :r_kv], pos + 1,
                            kv_map=kvm, axis_name=axis_name, scale=scale,
                            impl=impl, value_slice=r_kv)
    return o_c, cache_kv


def mla_attn_decode(p, x, cache_kv, pos, cfg, dims, *, policy=None,
                    core_wrap=None, attn_impl="ref"):
    """cache_kv: [B, S_loc, 1, r_kv+dr] compressed cache; pos scalar or [B]."""
    import functools
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((B, 1), pos,
                                                            jnp.int32)
    q_eff = _mla_q_eff(p, x, cfg, dims, positions, policy)[:, 0]  # [B,H,r+dr]
    kv = _mla_kv_stream(p, x, cfg, positions, policy)             # [B,1,r+dr]
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + dr)
    core = functools.partial(mla_decode_core, r_kv=r_kv, scale=scale,
                             impl=attn_impl)
    if core_wrap is not None:
        core = core_wrap(core)
    o_c, cache_kv = core(q_eff, kv[:, :, None, :], cache_kv, pos)
    out = _mla_out(p, o_c[:, None], cfg, dims, policy)
    return out, cache_kv


def mla_decode_core_chunk(q_eff, kv_new, cache_kv, pos, nvalid, *, r_kv,
                          scale, axis_name=None, impl="ref"):
    """Chunked absorbed-MLA core. q_eff [B, c, H, r_kv+dr]; kv_new
    [B, c, 1, r_kv+dr]; cache_kv [B, S_loc, 1, r_kv+dr]."""
    cache_kv = cache_insert_chunk(cache_kv, kv_new, pos, nvalid, axis_name)
    kvm = np.zeros((q_eff.shape[2],), np.int32)
    lengths = chunk_lengths(pos, nvalid, q_eff.shape[1])
    o_c = attend_contiguous(q_eff, cache_kv, cache_kv[..., :r_kv], lengths,
                            kv_map=kvm, axis_name=axis_name, scale=scale,
                            impl=impl, value_slice=r_kv)
    return o_c, cache_kv


def mla_attn_decode_chunk(p, x, cache_kv, pos, nvalid, cfg, dims, *,
                          policy=None, core_wrap=None, attn_impl="ref"):
    """Ragged multi-token MLA decode: x [B, c, D]; same contract as
    `gqa_attn_decode_chunk` on the compressed KV stream."""
    import functools
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    B, c, _ = x.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.maximum(pos[:, None] + jnp.arange(c, dtype=jnp.int32), 0)
    q_eff = _mla_q_eff(p, x, cfg, dims, positions, policy)   # [B, c, H, r+dr]
    kv = _mla_kv_stream(p, x, cfg, positions, policy)        # [B, c, r+dr]
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + dr)
    core = functools.partial(mla_decode_core_chunk, r_kv=r_kv, scale=scale,
                             impl=attn_impl)
    if core_wrap is not None:
        core = core_wrap(core)
    o_c, cache_kv = core(q_eff, kv[:, :, None, :], cache_kv, pos, nvalid)
    out = _mla_out(p, o_c, cfg, dims, policy)
    return out, cache_kv
