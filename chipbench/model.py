"""A configuration as the benchmark reads it, and its weights drawn from a seed.

Nothing here imports the program under test. The weight maker
(`system.build_params`) and the plain reference (`reference.py`) both draw
their floats from `layer_floats` / `outer_floats`, so the reference sees the
same bf16 checkpoint the program packs without taking anything the program
made.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

BENCH = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(BENCH, "configs")

# what the weight maker and the reference build besides the sizes: a file
# that states otherwise is refused by `load_model`, naming the key
BUILT = {"tie_word_embeddings": False, "use_sliding_window": False,
         "hidden_act": "silu"}


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes, formats and lowering of one configuration file."""

    name: str
    arch: str               # the program's registry name
    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    attention_bias: bool    # q/k/v projections carry biases
    rope_scaling: Optional[Tuple[str, float]]   # ("linear", factor) or None
    weights: str            # weight scheme, e.g. fp5.33-e2m3
    kv: str                 # paged KV scheme, e.g. fp4.25-e2m2
    strategy: str           # mantissa-sharing strategy
    control_weights: str    # one precision step below `weights`
    control_kv: str         # one precision step below `kv`
    impl: str               # linear lowering
    cache_impl: str         # paged-attention lowering
    page_size: int
    prefill_chunk: int
    reduced: bool = False   # rehearsal size (CPU, interpret mode)
    chips: int = 1          # the cell's chips: the tensor-parallel degree


def rope_scaling(value) -> Optional[Tuple[str, float]]:
    """A ``rope_scaling`` as a file or a registry entry states it (absent,
    ``None``, ``{"type": "linear", "factor": f}`` or ``("linear", f)``),
    as ``("linear", f)`` or None; any other type is refused."""
    if value is None:
        return None
    if isinstance(value, dict):
        value = (value.get("type"), value.get("factor"))
    kind, factor = value
    if kind != "linear":
        raise SystemExit(f"rope_scaling of type {kind!r}: the benchmark "
                         f"builds only linear position scaling")
    return ("linear", float(factor))


def load_model(name: str, rehearse: bool = False, chips: int = 1) -> Model:
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        c = json.load(f)
    for k, v in BUILT.items():
        if c.get(k, v) != v:
            raise SystemExit(f"{name}: {k} = {c[k]!r}; the benchmark builds "
                             f"only {k} = {v!r}")
    sizes, lowering = dict(c), c["lowering"]
    if rehearse:
        sizes.update(c["rehearsal"])
        lowering = dict(lowering, impl="pallas_interpret",
                        cache_impl="pallas_interpret")
    q = c["quantization"]
    return Model(
        name=c["name"], arch=c["arch"],
        d_model=sizes["hidden_size"], n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        d_ff=sizes["intermediate_size"], vocab=sizes["vocab_size"],
        norm_eps=sizes["rms_norm_eps"], rope_theta=sizes["rope_theta"],
        attention_bias=c["attention_bias"],
        rope_scaling=rope_scaling(c.get("rope_scaling")),
        weights=q["weights"], kv=q["kv_cache"], strategy=q["strategy"],
        control_weights=q["control"]["weights"],
        control_kv=q["control"]["kv_cache"],
        impl=lowering["impl"], cache_impl=lowering["cache_impl"],
        page_size=lowering["page_size"],
        prefill_chunk=lowering["prefill_chunk"], reduced=rehearse,
        chips=chips)


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (more than int32 holds)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * jnp.float32(scale)


def layer_floats(key, m: Model, layer):
    """One decoder layer's float32 weights, ``[in, out]`` for every matrix.
    Biases and norm gains are drawn away from 0 and 1 so that every add and
    scale in the layer matters to the output. The draws keep one key each
    whatever the configuration: a bias-free one leaves ``bq/bk/bv`` unused."""
    D, H, KV, hd, F = m.d_model, m.n_heads, m.n_kv, m.head_dim, m.d_ff
    ks = jax.random.split(jax.random.fold_in(key, layer), 12)
    return {
        "ln1": 1.0 + _normal(ks[0], (D,), 0.1),
        "wq": _normal(ks[1], (D, H * hd), D ** -0.5),
        "bq": _normal(ks[2], (H * hd,), 0.1),
        "wk": _normal(ks[3], (D, KV * hd), D ** -0.5),
        "bk": _normal(ks[4], (KV * hd,), 0.1),
        "wv": _normal(ks[5], (D, KV * hd), D ** -0.5),
        "bv": _normal(ks[6], (KV * hd,), 0.1),
        "wo": _normal(ks[7], (H * hd, D), (H * hd) ** -0.5),
        "ln2": 1.0 + _normal(ks[8], (D,), 0.1),
        "w_gate": _normal(ks[9], (D, F), D ** -0.5),
        "w_up": _normal(ks[10], (D, F), D ** -0.5),
        "w_down": _normal(ks[11], (F, D), F ** -0.5),
    }


def outer_floats(key, m: Model):
    """Embedding [V, D], final norm gain [D] and LM head [D, V], float32."""
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    D, V = m.d_model, m.vocab
    return {
        "embed": _normal(ks[0], (V, D), 1.0),
        "final_norm": 1.0 + _normal(ks[1], (D,), 0.1),
        "lm_head": _normal(ks[2], (D, V), D ** -0.5),
    }


def bf16(tree):
    """The serving checkpoint: every float rounded once to bfloat16."""
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)
