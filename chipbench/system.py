"""The system under test: the program's serving engine, built and warmed.

This is the benchmark's only module that imports the program (`repro`):
its config registry, `quantize_params` for the weight maker, its serving
mesh and parameter placement, and `EngineConfig -> ServeEngine` with its
public calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from model import (BUILT, Model, bf16, layer_floats, outer_floats,
                   rope_scaling, seed_key)

# the program's activation names as a configuration file states them
HIDDEN_ACT = {"silu_glu": "silu"}
# the `Model` fields held to the registry, besides what `BUILT` fixes
HELD = ("d_model", "n_layers", "n_heads", "n_kv", "head_dim", "d_ff", "vocab",
        "norm_eps", "rope_theta", "attention_bias", "rope_scaling")


def program_config(m: Model):
    """The program's registry entry for ``m.arch``, held to every size and
    architectural key the configuration file states (the reference reads
    only the file): on a mismatch it exits naming each key that differs.
    The registry's RoPE scaling is read as ``getattr(cfg, "rope_scaling",
    None)``, in any form `model.rope_scaling` takes."""
    from repro.configs import get_config
    cfg = get_config(m.arch)
    if m.reduced:
        cfg = cfg.reduced()
    have = dict(d_model=cfg.d_model, n_layers=cfg.num_layers,
                n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                attention_bias=cfg.qkv_bias,
                rope_scaling=rope_scaling(getattr(cfg, "rope_scaling", None)),
                tie_word_embeddings=cfg.tie_embeddings,
                use_sliding_window=cfg.sliding_window > 0,
                hidden_act=HIDDEN_ACT.get(cfg.ffn_activation,
                                          cfg.ffn_activation),
                family=cfg.family, attention=cfg.attention)
    want = {k: getattr(m, k) for k in HELD}
    want.update(BUILT, family="dense", attention="gqa")
    bad = [k for k in have if have[k] != want[k]]
    if bad:
        raise SystemExit(f"{m.name}: " + "; ".join(
            f"{k}: the configuration file states {want[k]!r}, the program's "
            f"{m.arch} has {have[k]!r}" for k in bad))
    return cfg


def serving_mesh(m: Model):
    """The program's tensor-parallel mesh over the cell's chips; None on
    one chip, where the engine keeps its own single-device mesh."""
    if m.chips == 1:
        return None
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(m.chips)


def _placement(tree, mesh):
    """The program's engine-step placement of a (served or float) tree."""
    from repro.launch.sharding import params_shardings
    return params_shardings(tree, mesh, fsdp=False, serve_n_shard=True)


def _policy(m: Model):
    from repro.core.policy import QuantPolicy
    return QuantPolicy(scheme=m.weights, strategy=m.strategy, impl=m.impl,
                       min_elements=1 << 10)


def maker(m: Model, mesh=None):
    """The weight maker's two jitted programs, each taking the seed's key:
    one draws and packs every layer (the same shapes each time, so it
    compiles once), one draws the embedding, final norm and LM head.

    With a ``mesh`` both emit straight into the program's own placement on
    it, and each layer's float and packed temporaries are placed the same
    way, so no chip holds the whole model or one whole float layer."""
    from repro.models.common import quantize_params
    policy = _policy(m)

    def place(tree):
        if mesh is None:
            return tree
        return jax.lax.with_sharding_constraint(tree, _placement(tree, mesh))

    def attn(b, n):
        w = {"w": b[f"w{n}"]}
        if m.attention_bias:
            w["b"] = b[f"b{n}"]
        return w

    def group(key, layer):
        b = bf16(layer_floats(key, m, layer))
        return place(quantize_params(place({"sub0": {
            "ln1": b["ln1"],
            "attn": {"wq": attn(b, "q"), "wk": attn(b, "k"),
                     "wv": attn(b, "v"), "wo": {"w": b["wo"]}},
            "ln2": b["ln2"],
            "ffn": {"w_gate": {"w": b["w_gate"]}, "w_up": {"w": b["w_up"]},
                    "w_down": {"w": b["w_down"]}},
        }}), policy))

    def layers(key):
        return jax.lax.map(lambda i: group(key, i),
                           jnp.arange(m.n_layers, dtype=jnp.int32))

    def outer(key):
        o = bf16(outer_floats(key, m))
        return {"embed": {"w": o["embed"]}, "final_norm": o["final_norm"],
                "lm_head": {"w": o["lm_head"]}}

    if mesh is None:
        return jax.jit(layers), jax.jit(outer)
    key = seed_key(0)
    stacked = _placement({"layers": jax.eval_shape(layers, key)}, mesh)
    return (jax.jit(layers, out_shardings=stacked["layers"]),
            jax.jit(outer, out_shardings=_placement(
                jax.eval_shape(outer, key), mesh)))


def build_params(m: Model, cfg, seed: int):
    """The served tree, made on the cell's chips from ``seed`` by `maker`,
    and held to the structure, shapes and dtypes the program's step expects
    at the cell's tensor-parallel degree."""
    from repro.configs.base import RunConfig
    from repro.launch.steps import quantized_param_shapes
    layers, outer = maker(m, serving_mesh(m))
    key = seed_key(seed)
    stacked = layers(key)      # first: its temporaries peak with nothing else live
    params = dict(outer(key), layers=stacked)
    want = quantized_param_shapes(
        cfg, RunConfig(model=cfg, mode="decode", quant=_policy(m)), m.chips)
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
        prefill_chunk=m.prefill_chunk, mesh=serving_mesh(m),
        cache=CacheConfig(kind="paged_ams", page_size=m.page_size,
                          num_pages=tr.get("num_pages", 0), kv_scheme=m.kv,
                          kv_strategy=m.strategy, impl=m.cache_impl))
    return ServeEngine(ec, params=params)
