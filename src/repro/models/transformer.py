"""Generic decoder assembly for all 10 assigned architecture families.

A model is a repeating *pattern* of blocks (len-1 for uniform families;
('rec','rec','attn') for recurrentgemma). Full pattern repeats are scanned
(lax.scan over stacked params, with optional remat); the remainder layers are
unrolled. The same block functions serve training (full-sequence), prefill
(full-sequence + cache emission) and decode (single token + cache update),
in either bf16 training precision or the AMS-quantized serving path.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as A
from . import ffn as F
from . import moe as M
from . import ssm as S
from .common import (Dims, apply_linear, cast_float_weights, make_linear,
                     make_norm, model_dims, quantize_params, rms_norm)
from .parallel import ParallelCtx


# ---------------------------------------------------------------------------
# Pattern / init
# ---------------------------------------------------------------------------
def layer_pattern(cfg) -> Tuple[str, ...]:
    if cfg.family == "hybrid":
        return cfg.block_pattern
    if cfg.family == "ssm":
        return ("mamba",)
    if cfg.family == "moe":
        return ("gqa_moe",)
    if cfg.attention == "mla":
        return ("mla",)
    return ("gqa",)


def init_block(key, cfg, dims: Dims, kind: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if kind == "mamba":
        return {"ln1": make_norm(cfg.d_model, dtype),
                "mixer": S.init_mamba(ks[0], cfg, dtype)}
    if kind == "rec":
        return {"ln1": make_norm(cfg.d_model, dtype),
                "mixer": S.init_rglru(ks[0], cfg, dtype),
                "ln2": make_norm(cfg.d_model, dtype),
                "ffn": F.init_ffn(ks[1], cfg.d_model, cfg.d_ff,
                                  cfg.ffn_activation, dtype)}
    if kind == "mla":
        return {"ln1": make_norm(cfg.d_model, dtype),
                "attn": A.init_mla(ks[0], cfg, dims, dtype),
                "ln2": make_norm(cfg.d_model, dtype),
                "ffn": F.init_ffn(ks[1], cfg.d_model, cfg.d_ff,
                                  cfg.ffn_activation, dtype)}
    if kind == "gqa_moe":
        return {"ln1": make_norm(cfg.d_model, dtype),
                "attn": A.init_gqa(ks[0], cfg, dims, dtype),
                "ln2": make_norm(cfg.d_model, dtype),
                "moe": M.init_moe(ks[1], cfg, dtype)}
    if kind in ("gqa", "attn"):
        return {"ln1": make_norm(cfg.d_model, dtype),
                "attn": A.init_gqa(ks[0], cfg, dims, dtype),
                "ln2": make_norm(cfg.d_model, dtype),
                "ffn": F.init_ffn(ks[1], cfg.d_model, cfg.d_ff,
                                  cfg.ffn_activation, dtype)}
    raise ValueError(f"unknown block kind {kind!r}")


def init_params(key, cfg, tp: int = 1, dtype=jnp.float32, *,
                map_layers=None):
    """Full parameter pytree. Pattern repeats stacked [G, ...] under 'layers';
    remainder blocks unrolled under 'tail'. ``map_layers(init_group, keys)``
    builds the stacked groups (default: ``vmap`` over the group keys)."""
    dims = model_dims(cfg, tp)
    pat = layer_pattern(cfg)
    L, Pn = cfg.num_layers, len(pat)
    G, R = L // Pn, L % Pn
    k_emb, k_layers, k_tail, k_head = jax.random.split(key, 4)

    def init_group(k):
        kk = jax.random.split(k, Pn)
        return {f"sub{i}": init_block(kk[i], cfg, dims, pat[i], dtype)
                for i in range(Pn)}

    map_layers = map_layers or (lambda f, keys: jax.vmap(f)(keys))
    params: Dict[str, Any] = {
        "embed": {"w": (jax.random.normal(k_emb, (dims.V, cfg.d_model),
                                          jnp.float32) * 0.02).astype(dtype)},
        "layers": map_layers(init_group, jax.random.split(k_layers, G)),
        "final_norm": make_norm(cfg.d_model, dtype),
        "lm_head": make_linear(k_head, cfg.d_model, dims.V, dtype=dtype),
    }
    if R:
        kk = jax.random.split(k_tail, R)
        params["tail"] = {f"sub{i}": init_block(kk[i], cfg, dims, pat[i], dtype)
                          for i in range(R)}
    return params


def init_serving_params(key, cfg, tp: int = 1, quant=None):
    """The serving engine's weights: `init_params` drawn straight into
    bf16 and, given a `QuantPolicy`, AMS-quantized by `quantize_params`.
    The values are bit-identical to
    ``quantize_params(cast_float_weights(init_params(key, cfg, tp)), quant)``
    (each weight is drawn in f32 and rounded once either way); only the
    rank-1 leaves outside the layer stack (norm gains, zero biases) are
    bf16 here where that path keeps f32, with the same values.

    The layer groups are generated, cast and quantized ONE AT A TIME and
    written into preallocated stacked leaves, so the peak is the result
    plus one group's temporaries rather than the whole float tree:
    qwen2-7b's bf16 tree (15.2 GB) never exists, only its 6.5 GB packed
    form. The groups run op by op, as the whole-tree path does: under
    ``jit`` XLA folds the init scale into the sampler's own arithmetic,
    turns the channel-scale division by a constant into a multiply and
    fuses the quantizer's error sums, which moves low bits and flips
    shared-LSB choices."""
    def serve(tree, min_ndim):
        # leaves a block creates in f32 on purpose are cast as the stacked
        # whole-tree path casts them (a group leaf of rank r stacks to r+1)
        tree = cast_float_weights(tree, min_ndim=min_ndim)
        return quantize_params(tree, quant) if quant is not None else tree

    put = jax.jit(lambda stacked, g, grp: jax.tree.map(
        lambda a, b: a.at[g].set(b), stacked, grp), donate_argnums=(0,))

    def map_layers(init_group, keys):
        stacked = None
        for g in range(keys.shape[0]):
            grp = serve(init_group(keys[g]), 1)
            if stacked is None:
                stacked = jax.tree.map(
                    lambda a: jnp.zeros((keys.shape[0],) + a.shape, a.dtype),
                    grp)
            stacked = put(stacked, g, grp)
        return stacked

    params = init_params(key, cfg, tp, jnp.bfloat16, map_layers=map_layers)
    rest = serve({k: v for k, v in params.items() if k != "layers"}, 2)
    return {**rest, "layers": params["layers"]}


# ---------------------------------------------------------------------------
# Block application — full sequence (train / prefill)
# ---------------------------------------------------------------------------
def block_seq(p, x, kind, cfg, dims, *, policy=None, ctx: Optional[ParallelCtx],
              block_kv=1024, prefix_len=0, want_cache=False):
    """Returns (x, aux_loss, cache_or_None)."""
    aux = jnp.float32(0)
    cache = None
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mamba":
        out, (conv_st, ssm_st) = S.mamba_train(p["mixer"], h, cfg, policy=policy)
        x = x + out
        if want_cache:
            cache = {"conv": conv_st, "ssm": ssm_st}
        return x, aux, cache
    if kind == "rec":
        out, (conv_st, rec_st) = S.rglru_train(p["mixer"], h, cfg, policy=policy)
        x = x + out
        if want_cache:
            cache = {"conv": conv_st, "state": rec_st}
    elif kind == "mla":
        out, kv = A.mla_attn_train(p["attn"], h, cfg, dims, policy=policy,
                                   block_kv=block_kv, prefix_len=prefix_len)
        x = x + out
        if want_cache:
            cache = {"kv": kv[:, :, None, :]}
    else:  # gqa / attn / gqa_moe
        window = cfg.sliding_window if kind == "attn" else 0
        out, (k, v) = A.gqa_attn_train(p["attn"], h, cfg, dims, policy=policy,
                                       block_kv=block_kv, prefix_len=prefix_len,
                                       window=window)
        x = x + out
        if want_cache:
            if window:
                k, v = (_to_ring(t, window) for t in (k, v))
            cache = {"k": k, "v": v}
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "gqa_moe":
        y, aux = M.moe_apply(p["moe"], h2, cfg, ctx, policy, phase="seq")
        x = x + y
    else:
        x = x + F.ffn_apply(p["ffn"], h2, cfg.ffn_activation, policy)
    return x, aux, cache


def _to_ring(kv: jnp.ndarray, window: int) -> jnp.ndarray:
    """Last `window` entries of [B, S, kv, hd] laid out by position % window."""
    B, Skv = kv.shape[0], kv.shape[1]
    W = min(window, Skv)
    tail = kv[:, Skv - W:]
    idx = (jnp.arange(Skv - W, Skv)) % window
    ring = jnp.zeros((B, window) + kv.shape[2:], kv.dtype)
    return ring.at[:, idx].set(tail)


# ---------------------------------------------------------------------------
# Block application — single-token decode
# ---------------------------------------------------------------------------
def _seq_core_wrap(ctx: ParallelCtx, n_caches: int):
    """shard_map wrapper for the insert+attend core with seq-sharded cache."""
    tp = ctx.tp_axis
    if n_caches == 2:  # gqa: (q, k_new, v_new, ck, cv, pos)
        in_specs = (P(None, None, None), P(None, None, None, None),
                    P(None, None, None, None),
                    P(None, tp, None, None), P(None, tp, None, None), P())
        out_specs = (P(None, None, None),
                     P(None, tp, None, None), P(None, tp, None, None))
    else:  # mla: (q_eff, kv_new, cache, pos)
        in_specs = (P(None, None, None), P(None, None, None, None),
                    P(None, tp, None, None), P())
        out_specs = (P(None, None, None), P(None, tp, None, None))

    def wrap(core):
        return ctx.shard_map(functools.partial(core, axis_name=tp),
                             in_specs=in_specs, out_specs=out_specs)
    return wrap


def pool_head_sharded(ctx: Optional[ParallelCtx], pool) -> bool:
    """True when the paged pool should run head-sharded over the model
    axis: a real tp>1 mesh and a kv-head count (axis ndim-2 of every pool
    plane) the axis divides. Non-divisible head counts stay replicated —
    the engine's `pool_shardings` applies the same rule, so the shard_map
    wrap and the pool placement always agree."""
    if ctx is None or ctx.mesh is None or ctx.tp <= 1:
        return False
    kv = jax.tree_util.tree_leaves(pool)[0].shape[-2]
    return kv % ctx.tp == 0


def _paged_core_wrap(ctx: ParallelCtx, pool, chunked: bool):
    """shard_map wrapper for the paged insert+attend core with the page
    pool HEAD-SHARDED over the model axis.

    Every pool plane — bf16 ``k``/``v`` [P, page, kv, hd] and the packed
    AMS ``hi``/``lsb``/``scale`` planes alike — splits on its kv-head axis
    (ndim-2); q and the new K/V vectors split on their head axes (the
    group-major projection layout keeps each q-head group on the device
    holding its kv head); pos / nvalid / block tables replicate. Inside
    the region quantize, scatter-insert and attend all see LOCAL head
    slices, so no page is ever gathered or resharded — the mesh only moves
    decode-sized activations, never KV bytes."""
    tp = ctx.tp_axis
    pool_specs = jax.tree_util.tree_map(
        lambda leaf: P(*([None] * (leaf.ndim - 2)), tp, None), pool)
    head4 = P(None, None, tp, None)
    q_spec = head4 if chunked else P(None, tp, None)
    bt = P(None, None)
    if chunked:  # (q, k_new, v_new, pool, pos, block_tables, nvalid)
        in_specs = (q_spec, head4, head4, pool_specs, P(), bt, P())
    else:        # (q, k_new, v_new, pool, pos, block_tables)
        in_specs = (q_spec, head4, head4, pool_specs, P(), bt)
    out_specs = (q_spec, pool_specs)

    def wrap(core):
        return ctx.shard_map(core, in_specs=in_specs, out_specs=out_specs)
    return wrap


def _replicate_model(x, ctx: Optional[ParallelCtx]):
    """Pin an activation replicated over the model axis (batch stays on the
    DP axes). The bit-exact TP serving layout N-shards every linear, so
    after each residual add this constraint is the ONLY cross-device step:
    an exact all-gather of a decode-sized activation. It keeps the next
    rms_norm's f32 mean over D device-complete — a model-sharded D would
    split that reduction and change the f32 rounding order vs tp=1."""
    if ctx is None or ctx.mesh is None or ctx.tp <= 1:
        return x
    dp = ctx.dp_axes if ctx.dp_axes else None
    import numpy as np
    if dp is not None:
        n = int(np.prod([ctx.mesh.shape[a] for a in dp]))
        if x.shape[0] % n != 0:
            dp = None
    spec = P(*((dp,) + (None,) * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, spec))


def block_decode(p, x, cache, pos, kind, cfg, dims, *, policy=None,
                 ctx: Optional[ParallelCtx], block_tables=None,
                 cache_cfg=None):
    """x: [B, 1, D]. Returns (x, new_cache)."""
    seq_sharded = ctx is not None and ctx.mesh is not None and ctx.seq_shard_cache
    paged = cache_cfg is not None and cache_cfg.paged
    # contiguous-cache attention impl (ref | pallas | pallas_interpret):
    # routes the GQA/MLA cores through the fused attention template
    attn_impl = cache_cfg.impl if cache_cfg is not None else "ref"
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mamba":
        out, (conv_st, ssm_st) = S.mamba_decode(
            p["mixer"], h, cache["conv"], cache["ssm"], cfg, policy=policy)
        return x + out, {"conv": conv_st, "ssm": ssm_st}
    if kind == "rec":
        out, (conv_st, rec_st) = S.rglru_decode(
            p["mixer"], h, cache["conv"], cache["state"], cfg, policy=policy)
        x = x + out
        cache = {"conv": conv_st, "state": rec_st}
    elif kind == "mla":
        wrap = _seq_core_wrap(ctx, 1) if seq_sharded else None
        out, ckv = A.mla_attn_decode(p["attn"], h, cache["kv"], pos, cfg, dims,
                                     policy=policy, core_wrap=wrap,
                                     attn_impl=attn_impl)
        x = x + out
        cache = {"kv": ckv}
    elif paged:
        wrap = (_paged_core_wrap(ctx, cache, chunked=False)
                if pool_head_sharded(ctx, cache) else None)
        out, cache = A.gqa_attn_decode_paged(
            p["attn"], h, cache, pos, block_tables, cfg, dims,
            policy=policy, cache_cfg=cache_cfg, core_wrap=wrap)
        x = _replicate_model(x + out, ctx)
    else:
        window = cfg.sliding_window if kind == "attn" else 0
        wrap = _seq_core_wrap(ctx, 2) if seq_sharded else None
        out, (ck, cv) = A.gqa_attn_decode(
            p["attn"], h, cache["k"], cache["v"], pos, cfg, dims,
            policy=policy, core_wrap=wrap, window=window, ring=bool(window),
            attn_impl=attn_impl)
        x = x + out
        cache = {"k": ck, "v": cv}
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "gqa_moe":
        y, _ = M.moe_apply(p["moe"], h2, cfg, ctx, policy, phase="decode")
        x = x + y
    else:
        x = x + F.ffn_apply(p["ffn"], h2, cfg.ffn_activation, policy)
    if paged:
        x = _replicate_model(x, ctx)
    return x, cache


def _seq_core_wrap_chunk(ctx: ParallelCtx, n_caches: int):
    """shard_map wrapper for the CHUNKED insert+attend core (seq-sharded
    cache): same layout as `_seq_core_wrap` with a chunk dim on q/k/v and
    the extra replicated [B] nvalid arg."""
    tp = ctx.tp_axis
    P4 = P(None, None, None, None)
    if n_caches == 2:  # gqa: (q, k_new, v_new, ck, cv, pos, nvalid)
        in_specs = (P4, P4, P4,
                    P(None, tp, None, None), P(None, tp, None, None),
                    P(), P())
        out_specs = (P4, P(None, tp, None, None), P(None, tp, None, None))
    else:  # mla: (q_eff, kv_new, cache, pos, nvalid)
        in_specs = (P4, P4, P(None, tp, None, None), P(), P())
        out_specs = (P4, P(None, tp, None, None))

    def wrap(core):
        return ctx.shard_map(functools.partial(core, axis_name=tp),
                             in_specs=in_specs, out_specs=out_specs)
    return wrap


def block_decode_chunk(p, x, cache, pos, nvalid, kind, cfg, dims, *,
                       policy=None, ctx: Optional[ParallelCtx],
                       block_tables=None, cache_cfg=None):
    """Ragged multi-token analogue of `block_decode`: x [B, c, D], per-slot
    start positions ``pos`` [B] and valid counts ``nvalid`` [B]. Supports
    the pure-attention families only (gqa / gqa_moe / mla — see
    `check_chunked_support`); recurrent blocks need a serial state update
    per token and keep the one-token step. Returns (x, new_cache)."""
    if kind not in ("gqa", "gqa_moe", "mla"):
        raise NotImplementedError(
            f"chunked decode does not support {kind!r} blocks")
    seq_sharded = ctx is not None and ctx.mesh is not None and ctx.seq_shard_cache
    paged = cache_cfg is not None and cache_cfg.paged
    attn_impl = cache_cfg.impl if cache_cfg is not None else "ref"
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mla":
        wrap = _seq_core_wrap_chunk(ctx, 1) if seq_sharded else None
        out, ckv = A.mla_attn_decode_chunk(p["attn"], h, cache["kv"], pos,
                                           nvalid, cfg, dims, policy=policy,
                                           core_wrap=wrap,
                                           attn_impl=attn_impl)
        x = x + out
        cache = {"kv": ckv}
    elif paged:
        wrap = (_paged_core_wrap(ctx, cache, chunked=True)
                if pool_head_sharded(ctx, cache) else None)
        out, cache = A.gqa_attn_decode_paged_chunk(
            p["attn"], h, cache, pos, nvalid, block_tables, cfg, dims,
            policy=policy, cache_cfg=cache_cfg, core_wrap=wrap)
        x = _replicate_model(x + out, ctx)
    else:
        wrap = _seq_core_wrap_chunk(ctx, 2) if seq_sharded else None
        out, (ck, cv) = A.gqa_attn_decode_chunk(
            p["attn"], h, cache["k"], cache["v"], pos, nvalid, cfg, dims,
            policy=policy, core_wrap=wrap, attn_impl=attn_impl)
        x = x + out
        cache = {"k": ck, "v": cv}
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "gqa_moe":
        y, _ = M.moe_apply(p["moe"], h2, cfg, ctx, policy, phase="decode")
        x = x + y
    else:
        x = x + F.ffn_apply(p["ffn"], h2, cfg.ffn_activation, policy)
    if paged:
        x = _replicate_model(x, ctx)
    return x, cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------
def block_cache_shape(cfg, dims: Dims, kind: str, B: int, cap: int, dtype):
    if kind == "mamba":
        return {"conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.d_inner), dtype),
                "ssm": jnp.zeros((B, cfg.d_inner, cfg.ssm_state), jnp.float32)}
    if kind == "rec":
        return {"conv": jnp.zeros((B, 3, cfg.lru_width), dtype),
                "state": jnp.zeros((B, cfg.lru_width), jnp.float32)}
    if kind == "mla":
        c = cfg.kv_lora_rank + cfg.qk_rope_dim
        return {"kv": jnp.zeros((B, cap, 1, c), dtype)}
    S_cap = min(cap, cfg.sliding_window) if (kind == "attn" and cfg.sliding_window) else cap
    if kind == "attn" and cfg.sliding_window:
        S_cap = cfg.sliding_window
    return {"k": jnp.zeros((B, S_cap, dims.kv, dims.hd), dtype),
            "v": jnp.zeros((B, S_cap, dims.kv, dims.hd), dtype)}


def check_paged_support(cfg):
    """Paged KV caching covers plain GQA attention layers only (for now):
    sliding-window ring caches, MLA's compressed stream, and SSM/RG-LRU
    recurrent states keep their contiguous layouts (docs/paged_cache.md
    §Extensions)."""
    pat = layer_pattern(cfg)
    bad = [k for k in pat if k not in ("gqa", "gqa_moe")]
    if bad:
        raise NotImplementedError(
            f"paged KV cache supports gqa/gqa_moe layers only; "
            f"{cfg.name} has {sorted(set(bad))}")
    if cfg.sliding_window:
        raise NotImplementedError(
            "paged KV cache does not support sliding-window ring caches yet")


def check_chunked_support(cfg):
    """Chunked (multi-token) decode covers pure-attention families: plain
    GQA, MoE-GQA and absorbed MLA. Mamba / RG-LRU recurrences integrate
    state token-by-token (a masked multi-token recurrent scan is the
    documented next step), and sliding-window ring caches would need
    chunk-aware ring inserts — those families keep the one-token step."""
    pat = layer_pattern(cfg)
    bad = [k for k in pat if k not in ("gqa", "gqa_moe", "mla")]
    if bad:
        raise NotImplementedError(
            f"chunked prefill supports gqa/gqa_moe/mla layers only; "
            f"{cfg.name} has {sorted(set(bad))}")
    if cfg.sliding_window:
        raise NotImplementedError(
            "chunked prefill does not support sliding-window ring caches yet")


def make_cache(cfg, B: int, cap: int, tp: int = 1, dtype=jnp.bfloat16,
               cache_cfg=None):
    """Zero-initialized cache pytree matching the params layout.

    With a paged ``cache_cfg`` the per-layer KV leaves are PAGE POOLS
    (`repro.cache.pool` layout, no batch dim — slots address them through
    block tables); otherwise the fixed [B, cap] slot layout."""
    dims = model_dims(cfg, tp)
    pat = layer_pattern(cfg)
    L, Pn = cfg.num_layers, len(pat)
    G, R = L // Pn, L % Pn
    paged = cache_cfg is not None and cache_cfg.paged
    if paged:
        from repro.cache import make_gqa_page_pool
        check_paged_support(cfg)

    def block(kind):
        if paged:
            return make_gqa_page_pool(cache_cfg, dims.kv, dims.hd, dtype)
        return block_cache_shape(cfg, dims, kind, B, cap, dtype)

    def group():
        return {f"sub{i}": block(pat[i]) for i in range(Pn)}

    cache = {"layers": jax.tree.map(
        lambda a: jnp.broadcast_to(a, (G,) + a.shape).copy() if G else a, group())}
    if R:
        cache["tail"] = {f"sub{i}": block(pat[i]) for i in range(R)}
    return cache


def reset_cache_slot(cache, slot):
    """Zero batch row `slot` of every cache leaf (slot reuse in the engine).

    KV entries beyond a slot's length are masked by position anyway, but the
    SSM / RG-LRU recurrent states integrate whatever an idle slot was fed, so
    a freed slot must be cleared before a new request is admitted into it.
    Stacked pattern-repeat leaves carry batch at axis 1 ([G, B, ...]); tail
    leaves at axis 0.
    """
    out = {"layers": jax.tree.map(lambda l: l.at[:, slot].set(0),
                                  cache["layers"])}
    if "tail" in cache:
        out["tail"] = jax.tree.map(lambda l: l.at[slot].set(0), cache["tail"])
    return out


# ---------------------------------------------------------------------------
# Full model: train forward / prefill / decode
# ---------------------------------------------------------------------------
def _embed(params, tokens, cfg, dims, prefix_embeds=None, dtype=jnp.bfloat16,
           ctx: Optional[ParallelCtx] = None):
    x = params["embed"]["w"].astype(dtype)[tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(dtype), x], axis=1)
    return _constrain_batch(x, ctx)


def _constrain_batch(x, ctx: Optional[ParallelCtx]):
    """Pin the batch dim to the DP axes after the embedding gather.

    The gather of a model-sharded embedding table with data-sharded indices
    loses the batch sharding in SPMD propagation — without this constraint
    the whole model body runs replicated over `data` (measured: 16x
    redundant flops on every train cell)."""
    if ctx is None or ctx.mesh is None or not ctx.dp_axes:
        return x
    import numpy as np
    n = int(np.prod([ctx.mesh.shape[a] for a in ctx.dp_axes]))
    if x.shape[0] % n != 0:
        return x
    spec = P(*((ctx.dp_axes,) + (None,) * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, spec))


def _head(params, x, cfg, dims, policy=None):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = apply_linear(params["lm_head"], x, policy)
    return logits.astype(jnp.float32) + dims.vocab_mask_bias[None, None, :]


def forward_seq(params, tokens, cfg, *, tp=1, policy=None, ctx=None,
                remat=True, block_kv=1024, prefix_embeds=None,
                want_cache=False, dtype=jnp.bfloat16):
    """Full-sequence forward. Returns (logits, aux, cache_or_None).

    train: want_cache=False; prefill: want_cache=True (logits for last token
    come from the same pass)."""
    dims = model_dims(cfg, tp)
    pat = layer_pattern(cfg)
    L, Pn = cfg.num_layers, len(pat)
    G, R = L // Pn, L % Pn
    prefix_len = 0 if prefix_embeds is None else prefix_embeds.shape[1]
    x = _embed(params, tokens, cfg, dims, prefix_embeds, dtype, ctx=ctx)

    def group_fn(carry, gp):
        x, aux = carry
        caches = {}
        for i in range(Pn):
            x, a, c = block_seq(gp[f"sub{i}"], x, pat[i], cfg, dims,
                                policy=policy, ctx=ctx, block_kv=block_kv,
                                prefix_len=prefix_len, want_cache=want_cache)
            aux = aux + a
            if want_cache:
                caches[f"sub{i}"] = c
        return (x, aux), (caches if want_cache else None)

    fn = jax.checkpoint(group_fn) if remat else group_fn
    (x, aux), layer_caches = jax.lax.scan(fn, (x, jnp.float32(0)),
                                          params["layers"])
    cache = {"layers": layer_caches} if want_cache else None
    if R:
        tail_caches = {}
        for i in range(R):
            x, a, c = block_seq(params["tail"][f"sub{i}"], x, pat[i], cfg, dims,
                                policy=policy, ctx=ctx, block_kv=block_kv,
                                prefix_len=prefix_len, want_cache=want_cache)
            aux = aux + a
            if want_cache:
                tail_caches[f"sub{i}"] = c
        if want_cache:
            cache["tail"] = tail_caches
    logits = _head(params, x, cfg, dims, policy)
    return logits, aux, cache


def decode_step(params, token, cache, pos, cfg, *, tp=1, policy=None,
                ctx=None, dtype=jnp.bfloat16, embeds=None, embed_mask=None,
                block_tables=None, cache_cfg=None, nvalid=None, ndraft=None,
                n_logits=1):
    """One decode step. token: [B] int32; pos: scalar int32 (insert position)
    or [B] int32 per-slot positions (continuous-batching engine; a negative
    position marks an idle slot whose cache write is suppressed).

    RAGGED MULTI-TOKEN STEP: with token [B, C] int32 the step consumes a
    variable-length block per slot — ``pos`` [B] is each slot's START
    position and ``nvalid`` [B] its valid token count this tick (prefilling
    slots bring a prompt chunk, decoding slots bring 1, idle slots 0).
    Positions are derived in-step (pos + chunk index), intra-chunk causality
    is enforced through per-query attention lengths, and the returned logits
    are taken at each slot's LAST valid token. Pure-attention families only
    (`check_chunked_support`).

    With a paged ``cache_cfg``, ``block_tables`` [B, max_pages] int32 maps
    each slot's logical pages to physical pool pages (same row for every
    layer); the cache pytree holds page pools instead of slot tensors.

    ``embeds`` [B, D] + ``embed_mask`` [B] bool optionally override the token
    embedding per slot (``[B, C, D]`` / ``[B, C]`` in the ragged step) — the
    engine uses this to stream modality prefix embeddings (VLM patches /
    audio frames) through the same decode step during chunked prefill.

    SPECULATIVE SCORING (``n_logits`` = K+1 > 1, ragged step only): the
    chunk's last ``ndraft[b]`` tokens are DRAFT tokens; logits come back
    [B, K+1, V] at positions ``nvalid-1-ndraft .. nvalid-1`` (clipped into
    the chunk) — row j scores the token following draft j, row 0 is
    exactly the last-valid-token row the plain step returns, so slots with
    ``ndraft == 0`` (prefill / plain decode) are unchanged.

    Returns (logits [B, V] — or [B, n_logits, V] when n_logits > 1 —,
    new cache)."""
    if token.ndim == 2:
        return _decode_step_chunk(params, token, cache, pos, nvalid, cfg,
                                  tp=tp, policy=policy, ctx=ctx, dtype=dtype,
                                  embeds=embeds, embed_mask=embed_mask,
                                  block_tables=block_tables,
                                  cache_cfg=cache_cfg, ndraft=ndraft,
                                  n_logits=n_logits)
    if n_logits != 1:
        raise ValueError("n_logits > 1 requires the ragged [B, C] step")
    dims = model_dims(cfg, tp)
    pat = layer_pattern(cfg)
    L, Pn = cfg.num_layers, len(pat)
    G, R = L // Pn, L % Pn
    x = _embed(params, token[:, None], cfg, dims, None, dtype, ctx=ctx)
    if embeds is not None:
        mask = (embed_mask if embed_mask is not None
                else jnp.ones(token.shape, bool))
        x = jnp.where(mask[:, None, None], embeds[:, None, :].astype(x.dtype), x)

    # Caches ride the scan xs/ys (slice in, updated slice out). We also
    # tried carrying the stacked cache and updating per-layer slices in
    # place — it measured 2.3x WORSE (XLA rematerializes the carried-buffer
    # slices; scan's native xs/ys streaming is already the cheaper path).
    # See EXPERIMENTS.md §Perf (refuted iteration).
    def group_fn(x, xs):
        gp, gcache = xs
        new_caches = {}
        for i in range(Pn):
            x, nc = block_decode(gp[f"sub{i}"], x, gcache[f"sub{i}"], pos,
                                 pat[i], cfg, dims, policy=policy, ctx=ctx,
                                 block_tables=block_tables,
                                 cache_cfg=cache_cfg)
            new_caches[f"sub{i}"] = nc
        return x, new_caches

    x, new_layer_caches = jax.lax.scan(group_fn, x,
                                       (params["layers"], cache["layers"]))
    new_cache = {"layers": new_layer_caches}
    if R:
        tails = {}
        for i in range(R):
            x, nc = block_decode(params["tail"][f"sub{i}"], x,
                                 cache["tail"][f"sub{i}"], pos, pat[i], cfg,
                                 dims, policy=policy, ctx=ctx,
                                 block_tables=block_tables,
                                 cache_cfg=cache_cfg)
            tails[f"sub{i}"] = nc
        new_cache["tail"] = tails
    logits = _head(params, x, cfg, dims, policy)
    return logits[:, 0], new_cache


def _decode_step_chunk(params, token, cache, pos, nvalid, cfg, *, tp=1,
                       policy=None, ctx=None, dtype=jnp.bfloat16,
                       embeds=None, embed_mask=None, block_tables=None,
                       cache_cfg=None, ndraft=None, n_logits=1):
    """Ragged multi-token step body (see `decode_step`): token [B, C],
    pos/nvalid [B]. Returns (logits [B, V] at each slot's last valid
    token — or [B, n_logits, V] at the last ndraft+1 valid positions when
    speculating — and the new cache)."""
    dims = model_dims(cfg, tp)
    pat = layer_pattern(cfg)
    L, Pn = cfg.num_layers, len(pat)
    G, R = L // Pn, L % Pn
    nvalid = jnp.asarray(nvalid, jnp.int32)
    x = _embed(params, token, cfg, dims, None, dtype, ctx=ctx)    # [B, C, D]
    if embeds is not None:
        mask = (embed_mask if embed_mask is not None
                else jnp.ones(token.shape, bool))
        x = jnp.where(mask[:, :, None], embeds.astype(x.dtype), x)

    def group_fn(x, xs):
        gp, gcache = xs
        new_caches = {}
        for i in range(Pn):
            x, nc = block_decode_chunk(gp[f"sub{i}"], x, gcache[f"sub{i}"],
                                       pos, nvalid, pat[i], cfg, dims,
                                       policy=policy, ctx=ctx,
                                       block_tables=block_tables,
                                       cache_cfg=cache_cfg)
            new_caches[f"sub{i}"] = nc
        return x, new_caches

    x, new_layer_caches = jax.lax.scan(group_fn, x,
                                       (params["layers"], cache["layers"]))
    new_cache = {"layers": new_layer_caches}
    if R:
        tails = {}
        for i in range(R):
            x, nc = block_decode_chunk(params["tail"][f"sub{i}"], x,
                                       cache["tail"][f"sub{i}"], pos, nvalid,
                                       pat[i], cfg, dims, policy=policy,
                                       ctx=ctx, block_tables=block_tables,
                                       cache_cfg=cache_cfg)
            tails[f"sub{i}"] = nc
        new_cache["tail"] = tails
    # logits only at each slot's LAST valid token — the head (the widest
    # matmul in the step) never runs over discarded prefill positions.
    # Speculative scoring widens the gather to the last ndraft+1 valid
    # positions ([B, n_logits, D]); index 0 degenerates to the plain
    # last-valid row for slots with ndraft == 0, so non-speculating slots
    # see identical logits either way.
    if n_logits > 1:
        nd = (jnp.zeros_like(nvalid) if ndraft is None
              else jnp.asarray(ndraft, jnp.int32))
        sel = jnp.clip(nvalid[:, None] - 1 - nd[:, None]
                       + jnp.arange(n_logits, dtype=jnp.int32)[None, :],
                       0, token.shape[1] - 1)                     # [B, K+1]
        x_sel = jnp.take_along_axis(x, jnp.broadcast_to(
            sel[:, :, None], sel.shape + (x.shape[2],)), axis=1)  # [B, K+1, D]
        return _head(params, x_sel, cfg, dims, policy), new_cache
    last = jnp.clip(nvalid - 1, 0, token.shape[1] - 1)[:, None, None]
    x_last = jnp.take_along_axis(x, jnp.broadcast_to(
        last, (x.shape[0], 1, x.shape[2])), axis=1)               # [B, 1, D]
    logits = _head(params, x_last, cfg, dims, policy)
    return logits[:, 0], new_cache
