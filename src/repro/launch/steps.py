"""Jitted step functions: train_step (microbatched grad accumulation),
prefill_step, serve_step (decode) — with full production shardings.

This module is mesh-parametric: given a mesh + RunConfig it returns AOT-
lowerable jitted callables with explicit in/out shardings. The dry-run
lowers exactly these steps; the train/serve drivers execute them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.models import (
    ParallelCtx,
    decode_step,
    forward_seq,
    init_params,
    make_cache,
    model_dims,
)
from repro.models.common import cast_float_weights, quantize_params
from repro.optim import (
    AdamWConfig,
    apply_updates,
    compressed_psum,
    init_state,
    warmup_cosine,
)
from . import sharding as SH
from .mesh import dp_axes, tp_axis


# ---------------------------------------------------------------------------
# Context / helpers
# ---------------------------------------------------------------------------
def make_ctx(mesh, mode: str) -> ParallelCtx:
    return ParallelCtx(
        mesh=mesh,
        dp_axes=dp_axes(mesh),
        tp_axis=tp_axis(mesh),
        seq_shard_cache=(mode == "decode"),
    )


def batch_dp(mesh, global_batch: int):
    """The dp axes actually usable for this batch size (None if B too small)."""
    axes = dp_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and global_batch % n == 0:
        return axes
    # try data-only (drop pod)
    if "data" in axes and global_batch % mesh.shape["data"] == 0:
        return ("data",)
    return None


def _loss_fn(params, tokens, targets, cfg, rcfg: RunConfig, ctx, prefix,
             dims, dtype=jnp.bfloat16):
    logits, aux, _ = forward_seq(
        params, tokens, cfg, tp=ctx.tp if ctx else 1,
        ctx=ctx, remat=rcfg.remat, block_kv=rcfg.attn_block_kv,
        prefix_embeds=prefix, dtype=dtype)
    logits = logits[:, -targets.shape[1]:]  # skip prefix positions
    ls = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(ls, targets[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    return loss + 0.01 * aux, loss


# ---------------------------------------------------------------------------
# TRAIN
# ---------------------------------------------------------------------------
def build_train_step(mesh, cfg: ModelConfig, rcfg: RunConfig):
    """Returns (step_fn, in_shardings, out_shardings, arg_shapes).

    step_fn(params, opt_state, tokens, targets, step) -> (params, opt_state,
    metrics). Gradient accumulation over microbatches via lax.scan; the
    DP/FSDP reductions are XLA-inserted from the shardings, except with
    grad_compression='int8_ag' where the cross-pod reduction is explicit
    (shard_map) int8-compressed.
    """
    ctx = make_ctx(mesh, "train")
    dims = model_dims(cfg, ctx.tp)
    B, S = rcfg.global_batch, rcfg.seq_len
    dp = batch_dp(mesh, B)
    dp_n = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    micro = rcfg.microbatch or dp_n  # default: 1 sample per dp shard
    assert B % micro == 0
    n_micro = B // micro
    adamw = AdamWConfig(grad_clip=rcfg.grad_clip)

    prefix_n = cfg.num_prefix_embeds
    S_tok = S - prefix_n  # frontend stub occupies prefix positions

    compress = (rcfg.grad_compression == "int8_ag" and dp is not None
                and "pod" in dp)

    def accum_grads(p_bf16, tok_m, tgt_m, pre_m):
        """Microbatch-accumulated grads (f32) + mean loss."""
        def micro_fn(acc, xs):
            tok, tgt, pre = xs
            (l, _), g = jax.value_and_grad(
                lambda p: _loss_fn(p, tok, tgt, cfg, rcfg, ctx, pre, dims),
                has_aux=True)(p_bf16)
            acc_g, acc_l = acc
            return (jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 acc_g, g), acc_l + l), None

        g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p_bf16)
        nm = tok_m.shape[0]
        (grads, loss_sum), _ = jax.lax.scan(micro_fn, (g0, jnp.float32(0)),
                                            (tok_m, tgt_m, pre_m))
        return (jax.tree.map(lambda g: g / nm, grads), loss_sum / nm)

    def accum_grads_podwise(p_bf16, tok_m, tgt_m, pre_m):
        """Pod axis manual: local grads, then an EXPLICIT int8-compressed
        cross-pod all-reduce (the all-gather half rides int8)."""
        npod = mesh.shape["pod"]

        def inner(p, tok, tgt, pre):
            g, l = accum_grads(p, tok, tgt, pre)
            g = compressed_psum(jax.tree.map(lambda x: x / npod, g), ("pod",))
            return g, jax.lax.pmean(l, "pod")

        p_specs = jax.tree.map(lambda _: P(), p_bf16)
        g_specs = jax.tree.map(lambda _: P(), p_bf16)
        data_spec = P(None, "pod", None)
        pre_spec = P(None, "pod", None, None)
        f = jax.shard_map(
            inner, mesh=mesh, axis_names={"pod"},
            in_specs=(p_specs, data_spec, data_spec, pre_spec),
            out_specs=(g_specs, P()), check_vma=False)
        return f(p_bf16, tok_m, tgt_m, pre_m)

    def step_fn(params, opt_state, tokens, targets, prefix, step):
        p_bf16 = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 and x.ndim >= 2 else x,
            params)
        tok_m = tokens.reshape(n_micro, micro, S_tok)
        tgt_m = targets.reshape(n_micro, micro, S_tok)
        pre_m = prefix.reshape(n_micro, micro, prefix_n, cfg.d_model)
        if dp:
            shard = NamedSharding(mesh, P(None, dp, None))
            tok_m = jax.lax.with_sharding_constraint(tok_m, shard)
            tgt_m = jax.lax.with_sharding_constraint(tgt_m, shard)
            pre_m = jax.lax.with_sharding_constraint(
                pre_m, NamedSharding(mesh, P(None, dp, None, None)))
        if compress:
            grads, loss = accum_grads_podwise(p_bf16, tok_m, tgt_m, pre_m)
        else:
            grads, loss = accum_grads(p_bf16, tok_m, tgt_m, pre_m)

        lr = warmup_cosine(step, rcfg.learning_rate, rcfg.warmup_steps, 10_000)
        params, opt_state, om = apply_updates(params, grads, opt_state, lr, adamw)
        metrics = {"loss": loss, "lr": lr, **om}
        return params, opt_state, metrics

    # --- shardings
    pshape = jax.eval_shape(
        lambda k: init_params(k, cfg, tp=ctx.tp), jax.random.PRNGKey(0))
    p_shard = SH.params_shardings(pshape, mesh, fsdp=rcfg.fsdp, moe="tp")
    o_shard = {"m": p_shard, "v": p_shard,
               "step": NamedSharding(mesh, P())}
    tok_shard = NamedSharding(mesh, P(dp, None))
    pre_shard = NamedSharding(mesh, P(dp, None, None))
    scalar = NamedSharding(mesh, P())
    # host-fed data args stay auto-sharded at the jit boundary (constraints
    # inside pin them); the dry-run's abstract args carry shardings instead.
    in_shardings = (p_shard, o_shard, None, None, None, None)
    out_shardings = (p_shard, o_shard,
                     jax.tree.map(lambda _: scalar,
                                  {"loss": 0, "lr": 0, "grad_norm": 0}))
    arg_shapes = dict(
        params=pshape,
        opt_state=jax.eval_shape(init_state, pshape),
        tokens=jax.ShapeDtypeStruct((B, S_tok), jnp.int32, sharding=tok_shard),
        targets=jax.ShapeDtypeStruct((B, S_tok), jnp.int32, sharding=tok_shard),
        prefix=jax.ShapeDtypeStruct((B, prefix_n, cfg.d_model), jnp.float32,
                                    sharding=pre_shard),
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
    )
    jitted = jax.jit(step_fn, in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     donate_argnums=(0, 1))
    return jitted, arg_shapes, dict(params=p_shard, opt_state=o_shard,
                                    tokens=tok_shard, targets=tok_shard,
                                    prefix=pre_shard, step=scalar)


# ---------------------------------------------------------------------------
# SERVE: prefill + decode
# ---------------------------------------------------------------------------
def quantized_param_shapes(cfg: ModelConfig, rcfg: RunConfig, tp: int):
    """Abstract shapes of the serving params (quantized per policy)."""
    def build(k):
        p = cast_float_weights(init_params(k, cfg, tp=tp, dtype=jnp.bfloat16))
        if rcfg.quantized:
            p = quantize_params(p, rcfg.quant)
        return p
    return jax.eval_shape(build, jax.random.PRNGKey(0))


def build_prefill_step(mesh, cfg: ModelConfig, rcfg: RunConfig):
    ctx = make_ctx(mesh, "prefill")
    dims = model_dims(cfg, ctx.tp)
    B, S = rcfg.global_batch, rcfg.seq_len
    dp = batch_dp(mesh, B)
    prefix_n = cfg.num_prefix_embeds
    S_tok = S - prefix_n
    policy = rcfg.quant if rcfg.quantized else None

    def prefill_fn(params, tokens, prefix):
        logits, _, cache = forward_seq(
            params, tokens, cfg, tp=ctx.tp, policy=policy, ctx=ctx,
            remat=False, block_kv=rcfg.attn_block_kv,
            prefix_embeds=prefix if prefix_n else None,
            want_cache=True, dtype=jnp.bfloat16)
        return logits[:, -1], cache

    pshape = quantized_param_shapes(cfg, rcfg, ctx.tp)
    p_shard = SH.params_shardings(pshape, mesh, fsdp=False)
    tok_shard = NamedSharding(mesh, P(dp, None))
    pre_shard = NamedSharding(mesh, P(dp, None, None))
    cache_shape = jax.eval_shape(
        lambda: make_cache(cfg, B, S, tp=ctx.tp, dtype=jnp.bfloat16))
    c_shard = SH.cache_shardings(cache_shape, mesh, dp=dp, seq_shard=True)
    out_shardings = (NamedSharding(mesh, P(dp, "model")), c_shard)
    jitted = jax.jit(prefill_fn,
                     in_shardings=(p_shard, None, None),
                     out_shardings=out_shardings)
    arg_shapes = dict(
        params=pshape,
        tokens=jax.ShapeDtypeStruct((B, S_tok), jnp.int32, sharding=tok_shard),
        prefix=jax.ShapeDtypeStruct((B, prefix_n, cfg.d_model), jnp.float32,
                                    sharding=pre_shard),
    )
    return jitted, arg_shapes, dict(params=p_shard, tokens=tok_shard,
                                    prefix=pre_shard)


def build_serve_step(mesh, cfg: ModelConfig, rcfg: RunConfig):
    """One decode step: (params, token [B], cache, pos) -> (logits, cache)."""
    ctx = make_ctx(mesh, "decode")
    B, S = rcfg.global_batch, rcfg.seq_len
    dp = batch_dp(mesh, B)
    policy = rcfg.quant if rcfg.quantized else None

    def serve_fn(params, token, cache, pos):
        return decode_step(params, token, cache, pos, cfg, tp=ctx.tp,
                           policy=policy, ctx=ctx, dtype=jnp.bfloat16)

    pshape = quantized_param_shapes(cfg, rcfg, ctx.tp)
    p_shard = SH.params_shardings(pshape, mesh, fsdp=False)
    cache_shape = jax.eval_shape(
        lambda: make_cache(cfg, B, S, tp=ctx.tp, dtype=jnp.bfloat16))
    c_shard = SH.cache_shardings(cache_shape, mesh, dp=dp, seq_shard=True)
    tok_shard = NamedSharding(mesh, P(dp))
    scalar = NamedSharding(mesh, P())
    jitted = jax.jit(
        serve_fn,
        in_shardings=(p_shard, None, c_shard, None),
        out_shardings=(NamedSharding(mesh, P(dp, "model")), c_shard),
        donate_argnums=(2,),
    )
    arg_shapes = dict(
        params=pshape,
        token=jax.ShapeDtypeStruct((B,), jnp.int32, sharding=tok_shard),
        cache=cache_shape,
        pos=jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P())),
    )
    return jitted, arg_shapes, dict(params=p_shard, token=tok_shard,
                                    cache=c_shard, pos=scalar)


def build_engine_step(mesh, cfg: ModelConfig, rcfg: RunConfig,
                      cache_cfg=None, chunk: int = 1,
                      sampling: bool = False, speculate_k: int = 0):
    """Slot-masked decode step for the continuous-batching engine.

    One tick serves every slot of the fixed-capacity KV cache at its OWN
    position: ``pos`` is [B] int32 per-slot insert positions (negative =
    idle slot; its cache write is suppressed and its output is garbage the
    engine ignores). Slots still consuming their prompt ride the same step
    as decoding slots and the engine discards their logits until the last
    prompt token.

    RAGGED MULTI-TOKEN STEP (``chunk`` = C > 1): every slot contributes a
    variable-length block of up to C tokens per tick — prefilling slots a
    prompt chunk, decoding slots 1, idle slots 0 — still as ONE jitted
    program. ``token`` becomes [B, C], ``pos`` [B] holds each slot's START
    position, and an extra [B] int32 ``nvalid`` arg (after pos) carries the
    per-slot valid length; logits are taken in-step at each slot's last
    valid token. Pure-attention families only (`check_chunked_support`).

    SAMPLING (``sampling=True``): the step's epilogue becomes the
    per-slot stochastic draw of `repro.launch.sampling` — temperature /
    top-k / top-p transforms and the categorical draw run ON DEVICE from
    per-slot folded PRNG keys, and termination (stop-token hit or length
    cap) is decided in-step. The step then takes one extra pytree arg
    ``sampling`` (see `sampling.slot_batch`: per-slot key/ngen/
    temperature/top_k/top_p/max_tokens/stop_ids rows) after the last
    positional input, and returns an extra [B] bool ``done`` flag. An
    all-greedy batch lowers to the exact argmax path via lax.cond, so
    greedy ticks are bit-identical to (and as cheap as) the
    ``sampling=False`` step. Per tick only [B] int32 tokens + [B] bools
    cross back to the host.

    Without sampling, greedy argmax runs on-device so each tick moves only
    [B] int32s back to the host scheduler.

    step(params, token [B] | [B, C], pos [B][, nvalid [B]], cache
         [, block_tables [B, MP]][, embeds, embed_mask][, sampling])
        -> (next_token [B][, done [B]], cache)

    The embeds override exists only when the config has a modality frontend
    (``num_prefix_embeds > 0``): prefix embeddings stream through the same
    step during prefill instead of a separate prefill program ([B, D] +
    [B] mask in the one-token step, [B, C, D] + [B, C] in the ragged step).

    SPECULATIVE DECODING (``speculate_k`` = K > 0, requires sampling and
    chunk >= K+1): a slot's chunk may end in up to K DRAFT tokens (an
    extra [B] int32 ``ndraft`` arg after nvalid carries the per-slot draft
    count; 0 = plain decode/prefill round, identical to before). The step
    scores all fed positions in the one ragged pass, runs the
    accept/resample rule of `repro.launch.speculative.verify_tokens` on
    device, zero-scatters the REJECTED suffix out of every cache leaf
    in-program (`speculative.truncate_cache` — so the cache the step hands
    back never contains rejected entries), and returns the whole emission:

        step(...) -> (out_tokens [B, K+1], n_emit [B], accepted [B],
                      done [B], cache)

    ``out_tokens[b, :n_emit[b]]`` are slot b's emitted tokens this round
    (accepted drafts + the bonus/corrective draw, truncated at an in-step
    stop/length hit); ``accepted`` is the raw accepted-draft count (the
    accept-rate statistic). Temperature-0 rows emit bit-exactly the
    non-speculative greedy stream; the host engine rewinds its feed
    position to ``pos + 1 + accepted``.

    With a paged ``cache_cfg`` (see `repro.cache.CacheConfig`), the cache
    pytree holds PAGE POOLS and the step takes the per-slot block tables as
    an extra [B, max_pages_per_seq] int32 arg after the cache. A block-table
    row may MIX pages: a shared (read-only, prefix-cached) page prefix
    followed by the slot's private insert-target pages. The step needs no
    distinction — reads walk the whole row, and writes only ever land in
    private pages because the engine starts each slot's positions at its
    cached length (asserted host-side per tick). The slot-masking contract
    is unchanged.

    TENSOR-PARALLEL (``mesh`` with model-axis size tp > 1): the paged step
    runs sharded with BIT-IDENTICAL streams to tp=1. Weight planes are
    placed by the serving layout (`sharding.params_shardings` with
    ``serve_n_shard=True`` — every linear N-sharded, so no contraction is
    ever split across devices), the page pools are HEAD-SHARDED over the
    model axis (`sharding.pool_shardings`; insert/truncate/attend run on
    local head slices under shard_map — pages never cross the mesh), the
    residual stream and the logits are pinned replicated so the f32
    norm/softmax reductions stay device-complete, and block tables /
    positions / per-slot lengths replicate. The host-side scheduler,
    `PageAllocator` and prefix-cache index are device-count-agnostic:
    page ids are head-dimension-free.

    A CONTIGUOUS ``cache_cfg`` threads through too: its ``impl`` field
    selects the attention lowering for the GQA/MLA decode cores ("ref" =
    the plain-XLA flash decode, default; "pallas"/"pallas_interpret" =
    the fused template of `kernels.attention_template`). Every cache mode
    x family x chunk combination therefore compiles through the same
    template module; impl is part of `engine_step_signature`.
    """
    ctx = make_ctx(mesh, "decode")
    paged = cache_cfg is not None and cache_cfg.paged
    if ctx.tp == 1 or paged:  # trivial model axis / pooled pages: no
        ctx = dataclasses.replace(ctx, seq_shard_cache=False)
    B, S = rcfg.global_batch, rcfg.seq_len
    dp = batch_dp(mesh, B)
    policy = rcfg.quant if rcfg.quantized else None
    has_prefix = cfg.num_prefix_embeds > 0
    chunked = chunk > 1
    if chunked:
        from repro.models import check_chunked_support
        check_chunked_support(cfg)
    spec = speculate_k > 0
    if spec and not sampling:
        raise ValueError("speculate_k requires sampling=True (the verify "
                         "rule subsumes the sampling epilogue)")
    if spec and chunk < speculate_k + 1:
        raise ValueError(
            f"speculate_k={speculate_k} needs chunk >= {speculate_k + 1} "
            f"(one fed token + k drafts per slot), got chunk={chunk}")

    def _rep_logits(logits):
        """Pin logits replicated over the model axis before the epilogue:
        sampling's softmax/cumsum (and verify's accept rule) reduce over
        the vocab dim — a model-sharded vocab would split those f32
        reductions and break bit-identity with tp=1. At tp=1: no-op."""
        if ctx.tp <= 1:
            return logits
        spec_ = P(*((dp,) + (None,) * (logits.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, spec_))

    def core(params, token, pos, cache, block_tables=None, embeds=None,
             embed_mask=None, nvalid=None, samp=None, ndraft=None):
        if spec:
            from repro.launch.speculative import truncate_cache, verify_tokens
            logits, cache = decode_step(
                params, token, cache, pos, cfg, tp=ctx.tp, policy=policy,
                ctx=ctx, dtype=jnp.bfloat16, embeds=embeds,
                embed_mask=embed_mask, block_tables=block_tables,
                cache_cfg=cache_cfg, nvalid=nvalid, ndraft=ndraft,
                n_logits=speculate_k + 1)
            logits = _rep_logits(logits)
            out, n_emit, accepted, done = verify_tokens(
                logits, token, nvalid, ndraft, samp, speculate_k)
            # un-insert the rejected suffix IN-PROGRAM: positions
            # pos+1+accepted .. pos+ndraft revert to pool-initial zeros,
            # so the returned cache never holds rejected entries and the
            # host's position rewind is all the rollback there is
            cache = truncate_cache(
                cache, pos + 1 + accepted,
                jnp.maximum(ndraft - accepted, 0), speculate_k,
                cache_cfg=cache_cfg, block_tables=block_tables)
            return out, n_emit, accepted, done, cache
        logits, cache = decode_step(
            params, token, cache, pos, cfg, tp=ctx.tp, policy=policy,
            ctx=ctx, dtype=jnp.bfloat16, embeds=embeds, embed_mask=embed_mask,
            block_tables=block_tables, cache_cfg=cache_cfg, nvalid=nvalid)
        logits = _rep_logits(logits)
        if samp is not None:
            from repro.launch.sampling import sample_tokens
            next_token, done = sample_tokens(logits, samp)
            return next_token, done, cache
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    pshape = quantized_param_shapes(cfg, rcfg, ctx.tp)
    p_shard = SH.params_shardings(pshape, mesh, fsdp=False,
                                  serve_n_shard=True)
    cache_shape = jax.eval_shape(
        lambda: make_cache(cfg, B, S, tp=ctx.tp, dtype=jnp.bfloat16,
                           cache_cfg=cache_cfg))
    if paged:
        # kv heads over the model axis (replicated fallback when they
        # don't divide it) — must agree with models.transformer's
        # pool_head_sharded/shard_map wrap, and it does: same rule
        c_shard = SH.pool_shardings(cache_shape, mesh)
    else:
        c_shard = SH.cache_shardings(cache_shape, mesh, dp=dp, seq_shard=True)
    tok_shard = NamedSharding(mesh, P(dp))

    # one signature for every (chunked, paged, prefix) combination: the
    # ordered arg-name list drives the closure, the shardings tuple AND the
    # donated cache index, so an optional input added here can never be
    # mis-threaded in one branch only
    arg_names = (["token", "pos"] + (["nvalid"] if chunked else [])
                 + (["ndraft"] if spec else [])
                 + ["cache"] + (["block_tables"] if paged else [])
                 + (["embeds", "embed_mask"] if has_prefix else [])
                 + (["sampling"] if sampling else []))

    def engine_fn(params, *args):
        kw = dict(zip(arg_names, args))
        return core(params, kw["token"], kw["pos"], kw["cache"],
                    kw.get("block_tables"), kw.get("embeds"),
                    kw.get("embed_mask"), kw.get("nvalid"),
                    kw.get("sampling"), kw.get("ndraft"))

    in_shardings = (p_shard,) + tuple(
        c_shard if n == "cache" else None for n in arg_names)
    tok2_shard = NamedSharding(mesh, P(dp, None))
    if spec:
        out_shardings = (tok2_shard, tok_shard, tok_shard, tok_shard, c_shard)
    elif sampling:
        out_shardings = (tok_shard, tok_shard, c_shard)
    else:
        out_shardings = (tok_shard, c_shard)
    # no excess precision: every bf16 value is rounded where the program
    # says so. A tensor-parallel step rounds its all-gathered activations
    # to bf16 regardless, so only this keeps its token streams
    # bit-identical to the single-device step's
    jitted = jax.jit(engine_fn, in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     donate_argnums=(1 + arg_names.index("cache"),),
                     compiler_options={"xla_allow_excess_precision": False})
    # arg_shapes preserves the jitted signature's POSITIONAL order — the
    # dry-run lowers via `jitted.lower(*arg_shapes.values())`
    tok_shape = (B, chunk) if chunked else (B,)
    arg_shapes = dict(
        params=pshape,
        token=jax.ShapeDtypeStruct(tok_shape, jnp.int32, sharding=tok_shard),
        pos=jax.ShapeDtypeStruct((B,), jnp.int32, sharding=tok_shard),
    )
    if chunked:
        arg_shapes["nvalid"] = jax.ShapeDtypeStruct((B,), jnp.int32,
                                                    sharding=tok_shard)
    if spec:
        arg_shapes["ndraft"] = jax.ShapeDtypeStruct((B,), jnp.int32,
                                                    sharding=tok_shard)
    arg_shapes["cache"] = cache_shape
    if paged:
        arg_shapes["block_tables"] = jax.ShapeDtypeStruct(
            (B, cache_cfg.max_pages_per_seq), jnp.int32)
    if has_prefix:
        emb_shape = (B, chunk, cfg.d_model) if chunked else (B, cfg.d_model)
        msk_shape = (B, chunk) if chunked else (B,)
        arg_shapes["embeds"] = jax.ShapeDtypeStruct(emb_shape, jnp.float32)
        arg_shapes["embed_mask"] = jax.ShapeDtypeStruct(msk_shape, jnp.bool_)
    if sampling:
        from repro.launch.sampling import batch_shapes
        arg_shapes["sampling"] = batch_shapes(B)
    shardings = dict(params=p_shard, token=tok_shard, pos=tok_shard,
                     cache=c_shard)
    if chunked:
        shardings["nvalid"] = tok_shard
    if spec:
        shardings["ndraft"] = tok_shard
    return jitted, arg_shapes, shardings


def engine_step_signature(cfg: ModelConfig, rcfg: RunConfig, cache_cfg=None,
                          chunk: int = 1, speculate_k: int = 0, mesh=None):
    """Canonical identity of one jitted engine-step program — the key the
    obs subsystem attributes per-tick cost under (`obs.cost`) and the
    label set exported on ``serve_step_signature_info``. Two engines with
    equal signatures compile the same step: cache mode x attention impl x
    chunk x speculate_k x weight scheme x slot count x mesh shape. ``impl``
    is the attention lowering ("ref" = plain-XLA flash decode, "pallas"/
    "pallas_interpret" = the fused template of
    `kernels.attention_template`) — it now applies to contiguous caches
    too, so it is part of the compiled program's identity. ``tp`` is the
    model-axis size of the serving mesh: a sharded step is a different
    program (per-device weight/KV residency — see `obs.cost`'s per-device
    floors) even though its token streams are bit-identical."""
    return dict(
        arch=cfg.name,
        scheme=rcfg.quant.scheme if rcfg.quantized else "fp16",
        cache=cache_cfg.kind if cache_cfg is not None else "contiguous",
        kv_scheme=(cache_cfg.kv_scheme
                   if cache_cfg is not None and cache_cfg.quantized else "bf16"),
        impl=cache_cfg.impl if cache_cfg is not None else "ref",
        slots=rcfg.global_batch,
        chunk=chunk,
        speculate_k=speculate_k,
        tp=(int(mesh.shape["model"])
            if mesh is not None and "model" in mesh.axis_names else 1),
    )


def build_step(mesh, cfg: ModelConfig, rcfg: RunConfig):
    if rcfg.mode == "train":
        return build_train_step(mesh, cfg, rcfg)
    if rcfg.mode == "prefill":
        return build_prefill_step(mesh, cfg, rcfg)
    if rcfg.mode == "decode":
        return build_serve_step(mesh, cfg, rcfg)
    raise ValueError(rcfg.mode)
