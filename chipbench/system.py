"""The system under test: the program's serving engine, built and warmed.

This is the benchmark's only module that imports the program (`repro`):
its config registry, `quantize_params` for the weight maker, and
`EngineConfig -> ServeEngine` with its public calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from model import Model, bf16, layer_floats, outer_floats, seed_key


def program_config(m: Model):
    """The program's registry entry for ``m.arch``, held to the sizes the
    configuration file states (the reference reads only the file)."""
    from repro.configs import get_config
    cfg = get_config(m.arch)
    if m.reduced:
        cfg = cfg.reduced()
    have = dict(d_model=cfg.d_model, n_layers=cfg.num_layers,
                n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta)
    want = {k: getattr(m, k) for k in have}
    shape = (cfg.family, cfg.attention, cfg.qkv_bias, cfg.ffn_activation,
             cfg.tie_embeddings, cfg.sliding_window)
    if have != want or shape != ("dense", "gqa", True, "silu_glu", False, 0):
        raise SystemExit(f"{m.name}: the program's {m.arch} is {have} {shape}, "
                         f"the configuration file states {want}")
    return cfg


def _policy(m: Model):
    from repro.core.policy import QuantPolicy
    return QuantPolicy(scheme=m.weights, strategy=m.strategy, impl=m.impl,
                       min_elements=1 << 10)


def maker(m: Model):
    """The weight maker's two jitted programs, each taking the seed's key:
    one draws and packs every layer (the same shapes each time, so it
    compiles once), one draws the embedding, final norm and LM head."""
    from repro.models.common import quantize_params
    policy = _policy(m)

    def group(key, layer):
        b = bf16(layer_floats(key, m, layer))
        return quantize_params({"sub0": {
            "ln1": b["ln1"],
            "attn": {"wq": {"w": b["wq"], "b": b["bq"]},
                     "wk": {"w": b["wk"], "b": b["bk"]},
                     "wv": {"w": b["wv"], "b": b["bv"]},
                     "wo": {"w": b["wo"]}},
            "ln2": b["ln2"],
            "ffn": {"w_gate": {"w": b["w_gate"]}, "w_up": {"w": b["w_up"]},
                    "w_down": {"w": b["w_down"]}},
        }}, policy)

    @jax.jit
    def layers(key):
        return jax.lax.map(lambda i: group(key, i),
                           jnp.arange(m.n_layers, dtype=jnp.int32))

    @jax.jit
    def outer(key):
        o = bf16(outer_floats(key, m))
        return {"embed": {"w": o["embed"]}, "final_norm": o["final_norm"],
                "lm_head": {"w": o["lm_head"]}}

    return layers, outer


def build_params(m: Model, cfg, seed: int):
    """The served tree, made on the device from ``seed`` by `maker`, and
    held to the structure, shapes and dtypes the program's step expects."""
    from repro.configs.base import RunConfig
    from repro.launch.steps import quantized_param_shapes
    layers, outer = maker(m)
    key = seed_key(seed)
    stacked = layers(key)      # first: its temporaries peak with nothing else live
    params = dict(outer(key), layers=stacked)
    want = quantized_param_shapes(
        cfg, RunConfig(model=cfg, mode="decode", quant=_policy(m)), 1)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise SystemExit(f"weight maker built {got}, the program wants {exp}")
    return jax.block_until_ready(params)


def make_engine(m: Model, tr: dict, params):
    from repro.cache import CacheConfig
    from repro.launch.config import EngineConfig
    from repro.launch.engine import ServeEngine
    ec = EngineConfig(
        arch=m.arch, reduced=m.reduced, scheme=m.weights, strategy=m.strategy,
        impl=m.impl, slots=tr["slots"], capacity=tr["capacity"],
        prefill_chunk=m.prefill_chunk,
        cache=CacheConfig(kind="paged_ams", page_size=m.page_size,
                          num_pages=tr.get("num_pages", 0), kv_scheme=m.kv,
                          kv_strategy=m.strategy, impl=m.cache_impl))
    return ServeEngine(ec, params=params)
