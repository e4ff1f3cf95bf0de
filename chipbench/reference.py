"""Plain float32 reference of a served decoder, and the gap check.

Written from the published descriptions (Qwen2 / Qwen1.5 and the Llama
block of DeepSeek-Coder: pre-norm RMSNorm, rotate-half RoPE, grouped-query
attention, SwiGLU MLP, untied LM head; q/k/v biases where the configuration
states ``attention_bias``, and RoPE positions divided by the factor of a
linear ``rope_scaling``, as Hugging Face's linear-scaling rotary embedding
does) and from the AMS-Quant formats, with nothing imported from the
program under test. It draws the same bf16 checkpoint as the weight
maker (`model.layer_floats`), quantizes it with its own quantizer, and runs
every matrix product at ``highest`` precision, one layer at a time so that
it fits beside nothing else on the chip.

What the served model computes, and so what the reference computes:

* each linear weight [K, N] is AMS-quantized once: per-output-channel scale
  ``max|W[:, n]| / max_normal``, round to nearest on the format's grid, then
  each group of ``k`` consecutive input rows shares its mantissa LSB, set to
  whichever of 0 and 1 gives the group the smaller squared error;
* each K and V vector is quantized once when it enters the cache, the same
  way along its head dimension, with one scale per (token, head);
* attention reads the quantized keys and values, its own token's included.

`gap_check` then reads, at each served position, how far the served token's
logit lies below the reference's best logit there, and averages it.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from model import Model, bf16, layer_floats, outer_floats, seed_key

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ formats
def parse_scheme(name: str):
    """``fp<bits>-e<E>m<M>`` -> (E, M, k): k is the mantissa-sharing group
    (1 when ``bits`` equals 1 + E + M, else ``1 / (bits - E - M)``)."""
    m = re.fullmatch(r"fp([0-9.]+)-e(\d)m(\d)", name)
    if not m:
        raise ValueError(f"unknown scheme {name!r}")
    bits, E, M = float(m.group(1)), int(m.group(2)), int(m.group(3))
    frac = bits - (E + M)
    k = 1 if abs(frac - 1.0) < 1e-6 else int(round(1.0 / frac))
    return E, M, k


@functools.lru_cache(maxsize=None)
def grid(E: int, M: int):
    """(magnitudes, midpoints, max_normal) of an E/M float format without
    Inf/NaN, exponent bias 2^(E-1) - 1."""
    bias = (1 << (E - 1)) - 1
    codes = np.arange(1 << (E + M))
    e, man = codes >> M, codes & ((1 << M) - 1)
    frac = man / float(1 << M)
    mags = np.where(e == 0, 2.0 ** (1 - bias) * frac,
                    2.0 ** (e - bias) * (1.0 + frac))
    mids = (mags[:-1] + mags[1:]) / 2.0
    return mags.astype(np.float32), mids.astype(np.float32), float(mags[-1])


def fake_quant(w, scheme: str):
    """Quantize-dequantize ``w`` [K, N] (float32) column by column, sharing
    mantissa LSBs along K; returns float32 [K, N]."""
    E, M, k = parse_scheme(scheme)
    mags, mids, top = grid(E, M)
    mags, mids = jnp.asarray(mags), jnp.asarray(mids)
    K, N = w.shape
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = amax / jnp.float32(top)
    scale = jnp.where(scale == 0, jnp.float32(1.0), scale)
    if K % k:
        w = jnp.pad(w, ((0, k - K % k), (0, 0)))
    wn = w / scale
    neg = wn < 0
    code = jnp.searchsorted(mids, jnp.abs(wn), side="right").astype(jnp.int32)
    if k > 1:
        def value(c):
            return jnp.where(neg, -mags[c], mags[c])

        c0, c1 = code & ~1, code | 1
        g = w.shape[0] // k
        e0 = ((value(c0) - wn) ** 2).reshape(g, k, N).sum(axis=1)
        e1 = ((value(c1) - wn) ** 2).reshape(g, k, N).sum(axis=1)
        pick1 = jnp.repeat(e1 < e0, k, axis=0)
        code = jnp.where(pick1, c1, c0)
    val = jnp.where(neg, -mags[code], mags[code]) * scale
    return val[:K]


def fake_quant_vectors(x, scheme: str):
    """Quantize-dequantize each vector of ``x`` [..., d] along its last axis
    with its own scale (the KV cache's per-(token, head) rule)."""
    lead, d = x.shape[:-1], x.shape[-1]
    flat = x.reshape(-1, d).T
    return fake_quant(flat, scheme).T.reshape(*lead, d)


# ------------------------------------------------------------------ forward
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta, scaling=None):
    """Rotate-half RoPE at positions ``pos``; a linear ``scaling``
    (``("linear", factor)``) divides the positions by its factor first."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    pos = pos.astype(jnp.float32)
    if scaling is not None:
        pos = pos / jnp.float32(scaling[1])
    ang = pos[..., None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("m", "wfmt", "kvfmt", "qblock"))
def _layer(key, layer, x, *, m: Model, wfmt: str, kvfmt: str, qblock: int):
    """One decoder layer over ``x`` [S, T, D]. Rows past a sequence's end
    are padding; attention is causal, so they never reach a valid row."""
    f = jax.tree.map(lambda a: a.astype(jnp.float32),
                     bf16(layer_floats(key, m, layer)))
    q_ = {n: fake_quant(f[n], wfmt)
          for n in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
    S, T, D = x.shape
    H, KV, hd = m.n_heads, m.n_kv, m.head_dim
    pos = jnp.arange(T)[None, :]
    h = _rms(x, f["ln1"], m.norm_eps)

    def proj(n, heads):
        y = _mm(h, q_[f"w{n}"])
        if m.attention_bias:
            y = y + f[f"b{n}"]
        return y.reshape(S, T, heads, hd)

    q, kk, v = proj("q", H), proj("k", KV), proj("v", KV)
    q = _rope(q, pos, m.rope_theta, m.rope_scaling)
    # the cache quantizes the bf16 activations the step hands it
    kk, v = (a.astype(jnp.bfloat16).astype(jnp.float32)
             for a in (_rope(kk, pos, m.rope_theta, m.rope_scaling), v))
    kk = fake_quant_vectors(kk, kvfmt)
    v = fake_quant_vectors(v, kvfmt)
    g = H // KV
    q = q.reshape(S, T, KV, g, hd) / jnp.float32(math.sqrt(hd))
    kpos = jnp.arange(T)

    def attend(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, qblock, axis=1)
        s = jnp.einsum("sqkgd,stkd->skgqt", qb, kk, precision=HIGHEST)
        qpos = start + jnp.arange(qblock)
        ok = kpos[None, :] <= qpos[:, None]
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("skgqt,stkd->sqkgd", p, v, precision=HIGHEST)

    starts = jnp.arange(0, T, qblock)
    o = jax.lax.map(attend, starts)                     # [nb, S, qb, KV, g, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(S, T, H * hd)
    x = x + _mm(o, q_["wo"])
    h = _rms(x, f["ln2"], m.norm_eps)
    y = jax.nn.silu(_mm(h, q_["w_gate"])) * _mm(h, q_["w_up"])
    return x + _mm(y, q_["w_down"])


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m: Model):
    e = bf16(outer_floats(key, m))["embed"]
    return e[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m",))
def _head(key, x, *, m: Model):
    o = jax.tree.map(lambda a: a.astype(jnp.float32), bf16(outer_floats(key, m)))
    return _mm(_rms(x, o["final_norm"], m.norm_eps), o["lm_head"])


def logits_at(m: Model, seed: int, seqs, want, length: int, *, wfmt=None,
              kvfmt=None, qblock: int = 512):
    """Reference logits [n, V] (float32, numpy) at the positions ``want``
    (a list of (sequence index, position)) of the token lists ``seqs``,
    padded to ``length`` rounded up to ``qblock`` (one shape per cell, so
    one compile). Formats default to the configuration's; the control
    passes lower ones."""
    wfmt = wfmt or m.weights
    kvfmt = kvfmt or m.kv
    key = seed_key(seed)
    qblock = min(qblock, length)
    T = -(-max(length, *(len(s) for s in seqs)) // qblock) * qblock
    toks = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    x = _embed(key, jnp.asarray(toks), m=m)
    for layer in range(m.n_layers):
        x = _layer(key, layer, x, m=m, wfmt=wfmt, kvfmt=kvfmt, qblock=qblock)
    # rows padded to a multiple of 128: the head compiles once per cell
    n = len(want)
    pad = want + [want[-1]] * (-n % 128)
    si = jnp.asarray([a for a, _ in pad], jnp.int32)
    pi = jnp.asarray([b for _, b in pad], jnp.int32)
    return np.asarray(_head(key, x[si, pi], m=m))[:n]


def served_positions(prompts, outputs):
    """(sequences, positions, served tokens): served token j of a request
    is read off the logits at position ``len(prompt) - 1 + j`` of
    prompt + output."""
    seqs, want, toks = [], [], []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        seqs.append(np.concatenate([np.asarray(p), np.asarray(o)]).astype(np.int32))
        for j, t in enumerate(o):
            want.append((i, len(p) - 1 + j))
            toks.append(int(t))
    return seqs, want, np.asarray(toks, np.int64)


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best logit at its position (0 where it is the best)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got


def gap_check(m: Model, seed: int, prompts, outputs, length: int):
    """The served tokens' gaps under the reference: (mean, widest)."""
    seqs, want, toks = served_positions(prompts, outputs)
    g = gaps(logits_at(m, seed, seqs, want, length), toks)
    return float(g.mean()), float(g.max())


def control_gaps(m: Model, seed: int, prompts, outputs, length: int):
    """Gaps (mean, widest) of the served tokens and of the control's on the
    same prompts and served tokens. The control is this reference computed
    one precision step below the configuration (``control_weights``,
    ``control_kv``); its gap is read at the token it ranks first."""
    seqs, want, toks = served_positions(prompts, outputs)
    ref = logits_at(m, seed, seqs, want, length)
    low = logits_at(m, seed, seqs, want, length, wfmt=m.control_weights,
                    kvfmt=m.control_kv)
    g, c = gaps(ref, toks), gaps(ref, low.argmax(axis=-1))
    return {"served_mean": float(g.mean()), "served_widest": float(g.max()),
            "control_mean": float(c.mean()), "control_widest": float(c.max()),
            "tokens": int(len(toks))}
