"""Shared model building blocks: norms, RoPE, quant-aware linears, padding.

Parameters are plain nested dicts of jnp arrays (pytrees). A "linear" is a
sub-dict: ``{'w': [K, N]}`` (+ optional ``'b': [N]``) in high precision, or —
after offline AMS-Quant PTQ — ``{'hi', 'lsb', 'scale'}`` packed planes
(+ optional ``'b'``). ``apply_linear`` dispatches on which keys are present,
so the same model code serves both the bf16 training path and the quantized
serving path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import get_scheme
from repro.core.packing import PackedWeight, make_layout
from repro.core.policy import QuantPolicy


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_heads(h: int, tp: int) -> int:
    """Pad a head count so it shards evenly over `tp`-way tensor parallelism."""
    return ceil_to(h, tp)


# --------------------------------------------------------------------- init
def make_linear(key, K: int, N: int, bias: bool = False,
                dtype=jnp.float32, scale: float | None = None) -> Dict[str, Any]:
    s = scale if scale is not None else 1.0 / np.sqrt(K)
    p = {"w": (jax.random.normal(key, (K, N), jnp.float32) * s).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((N,), dtype)
    return p


def make_norm(d: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.ones((d,), dtype)


# -------------------------------------------------------------------- apply
def apply_linear(p: Dict[str, Any], x: jnp.ndarray,
                 policy: Optional[QuantPolicy] = None) -> jnp.ndarray:
    """y = x @ W (+b); dispatches plain vs AMS-packed representation."""
    if "w" in p:
        y = x @ p["w"].astype(x.dtype)
    else:
        scheme = get_scheme(policy.scheme)
        lay = make_layout(scheme)
        K = x.shape[-1]
        N = p["scale"].shape[-1]
        pw = PackedWeight(p["hi"], p["lsb"], p["scale"], lay, K, N)
        impl = policy.impl
        if impl == "ref":
            from repro.kernels import ref
            w = (ref.dequant_full(pw, jnp.float32)).astype(x.dtype)
            y = x @ w
        elif impl == "fused_ref":
            from repro.kernels import ref
            lead = x.shape[:-1]
            y = ref.ams_matmul_blocked(x.reshape(-1, K), pw)
            y = y.reshape(*lead, N).astype(x.dtype)
        elif impl in ("pallas", "pallas_interpret"):
            from repro.kernels import ops
            y = ops.ams_matmul(x, pw, interpret=(impl == "pallas_interpret"))
            y = y.astype(x.dtype)
        else:
            raise ValueError(f"unknown quant impl {impl!r}")
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def materialize_weight(p: Dict[str, Any], K: int, dtype,
                       policy: Optional[QuantPolicy] = None) -> jnp.ndarray:
    """Return the [K, N] weight, dequantizing packed planes if needed.

    Used where the weight participates in non-matmul math (MLA absorbed
    einsums): the packed representation is still what lives in HBM."""
    if "w" in p:
        return p["w"].astype(dtype)
    from repro.kernels import ref
    scheme = get_scheme(policy.scheme)
    lay = make_layout(scheme)
    N = p["scale"].shape[-1]
    pw = PackedWeight(p["hi"], p["lsb"], p["scale"], lay, K, N)
    return ref.dequant_full(pw, jnp.float32).astype(dtype)


def rms_norm(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------- RoPE
def rope_angles(positions: jnp.ndarray, dim: int, theta: float) -> jnp.ndarray:
    """[..., dim/2] angles for given integer positions."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    return positions.astype(jnp.float32)[..., None] * inv


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, hd]; positions: [B, S] (or [S])."""
    hd = x.shape[-1]
    ang = rope_angles(positions, hd, theta)  # [B, S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :].astype(x.dtype)  # [B, S, 1, hd/2]
    sin = sin[..., None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ----------------------------------------------------------- quantize tree
def cast_float_weights(params, dtype=jnp.bfloat16, min_ndim: int = 2):
    """Cast high-precision weights to the serving dtype: floating leaves of
    rank >= ``min_ndim``. Packed AMS linears ({'hi', 'lsb', 'scale'}) are
    returned untouched — their int planes and f32 channel scales are the
    quantized representation itself, so a packed tree passes through."""
    def visit(node):
        if isinstance(node, dict):
            if "hi" in node:
                return node
            return {k: visit(v) for k, v in node.items()}
        if jnp.issubdtype(node.dtype, jnp.floating) and node.ndim >= min_ndim:
            return node.astype(dtype)
        return node

    return visit(params)


def quantize_params(params, policy: QuantPolicy, strategy: Optional[str] = None):
    """Offline PTQ pass: replace eligible {'w': [.., K, N]} linears by packed
    planes. Handles stacked leading dims (scan layers, MoE experts) via vmap.
    Biases/norms/small tensors stay in high precision.
    """
    from repro.core.ams import ams_quantize
    from repro.core.packing import pack

    scheme = get_scheme(policy.scheme)
    strategy = strategy or policy.strategy
    lay = make_layout(scheme)

    def quant_one(w2d):  # [K, N] -> dict of planes (padded K)
        K = w2d.shape[0]
        Kp = lay.padded_k(K)
        wp = jnp.pad(w2d.astype(jnp.float32), ((0, Kp - K), (0, 0)))
        codes, scale = ams_quantize(wp, scheme, strategy)
        pw = pack(codes, scale, scheme)
        return {"hi": pw.hi, "lsb": pw.lsb, "scale": pw.scale}

    def visit(path: str, node):
        if isinstance(node, dict) and "w" in node:
            w = node["w"]
            if w.ndim >= 2 and policy.wants(path, w.shape[-2:]):
                fn = quant_one
                for _ in range(w.ndim - 2):
                    fn = jax.vmap(fn)
                out = fn(w)
                if "b" in node:
                    out["b"] = node["b"]
                return out
            return node
        if isinstance(node, dict):
            return {k: visit(f"{path}/{k}", v) for k, v in node.items()}
        return node

    return visit("", params)


@dataclasses.dataclass(frozen=True)
class Dims:
    """Padded, mesh-aware derived dimensions for one model instance.

    Head layout is GROUP-MAJOR: q-head slot j belongs to kv group j // gp;
    the first `gt` slots of each group are real heads, the rest are padding
    (dead: masked after attention, zero grads through wo). When the padded
    q-head count doesn't divide by the true kv count (MHA archs on a 16-way
    TP mesh), kv heads are padded too — this keeps attention a pure grouped
    einsum with ZERO gather/expand materialization of K/V.

    NOTE: this permutes head order vs. the original checkpoints; a loader
    would apply the corresponding column permutation (documented in
    DESIGN.md).
    """

    tp: int
    H: int          # padded q-head count
    H_true: int
    kv: int         # padded kv-head count
    kv_true: int
    hd: int
    V: int          # padded vocab
    V_true: int

    @property
    def gp(self) -> int:  # q-head slots per kv group
        return self.H // self.kv

    @property
    def gt(self) -> int:  # real q heads per real kv group
        return self.H_true // self.kv_true

    @property
    def head_mask(self) -> jnp.ndarray:
        j = jnp.arange(self.H)
        return ((j // self.gp < self.kv_true)
                & (j % self.gp < self.gt)).astype(jnp.float32)

    @property
    def vocab_mask_bias(self) -> jnp.ndarray:
        """Additive -inf bias for padded vocab slots."""
        return jnp.where(jnp.arange(self.V) < self.V_true, 0.0, -1e9).astype(jnp.float32)


def model_dims(cfg, tp: int = 1, head_dim: Optional[int] = None) -> Dims:
    hd = head_dim if head_dim is not None else cfg.head_dim
    H_true = cfg.num_heads
    kv_true = max(1, cfg.num_kv_heads)
    Hp = pad_heads(H_true, tp)
    kv = kv_true if Hp % kv_true == 0 else Hp  # MHA-ish: pad kv alongside q
    return Dims(
        tp=tp,
        H=Hp,
        H_true=H_true,
        kv=kv,
        kv_true=kv_true,
        hd=hd,
        V=ceil_to(cfg.vocab_size, tp),
        V_true=cfg.vocab_size,
    )
