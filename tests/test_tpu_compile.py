"""TPU v5e compile rehearsals of the main path's kernels at qwen2-7b widths.

Interpret mode checks none of what the chip's compiler refuses (block
shapes that are not (8, 128)-aligned, scoped VMEM over the limit, ops
Mosaic cannot lower). These tests compile the Pallas kernels for a
described, unattached v5e (``jax.experimental.topologies``) so every PR
is checked against the real compiler at no chip time:

  * `ams_matmul` for fp5.33-e2m3 and fp4.25-e2m2 at every qwen2-7b linear
    shape, at one row and at the chip smoke's 64 rows (4 slots x chunk 16),
    with the kernel's VMEM limit set to the planner's estimate — so the
    estimate is proven to cover what the kernel holds;
  * the fused attention template, paged (bf16 and AMS pages) and
    contiguous, at chunk 1, 4 and 16, with qwen2-7b's 28 q / 4 kv heads.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU compiler. The persistent
compilation cache is off around these compiles — an entry written for a
described chip cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cache import CacheConfig, make_gqa_page_pool
from repro.core.formats import get_scheme
from repro.core.packing import PackedWeight, make_layout
from repro.kernels import ops
from repro.kernels.attention_template import (
    fused_contiguous_attention,
    fused_paged_attention,
)
from repro.kernels.tuning import VMEM_BYTES, plan_tiles

D, F, KV_OUT = 3584, 18944, 512          # qwen2-7b d_model, d_ff, kv*hd
H, KV, HD = 28, 4, 128
SLOTS, CAP, PAGE = 4, 544, 16
LINEARS = [(D, D), (D, KV_OUT), (D, F), (F, D)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("K,N", LINEARS)
@pytest.mark.parametrize("scheme", ["fp5.33-e2m3", "fp4.25-e2m2"])
def test_ams_matmul_compiles_for_v5e(one_chip, scheme, K, N):
    lay = make_layout(get_scheme(scheme))
    hi = _spec(one_chip, (lay.hi_rows(K), N), jnp.int32)
    lsb = _spec(one_chip, (lay.lsb_rows(K), N), jnp.int32)
    scale = _spec(one_chip, (N,), jnp.float32)
    for B in (1, SLOTS * 16):
        plan = plan_tiles(lay, B, K, N)
        assert plan.vmem_bytes <= VMEM_BYTES
        x = _spec(one_chip, (B, K), jnp.bfloat16)
        text = _compile(lambda x, h, l, s: ops.ams_matmul(
            x, PackedWeight(h, l, s, lay, K, N)), x, hi, lsb, scale)
        assert "tpu_custom_call" in text, (scheme, K, N, B)


@pytest.mark.parametrize("chunk", [1, 4, 16])
@pytest.mark.parametrize("kind", ["paged_bf16", "paged_ams", "contiguous"])
def test_attention_template_compiles_for_v5e(one_chip, kind, chunk):
    q_shape = (SLOTS, H, HD) if chunk == 1 else (SLOTS, chunk, H, HD)
    len_shape = (SLOTS,) if chunk == 1 else (SLOTS, chunk)
    q = _spec(one_chip, q_shape, jnp.bfloat16)
    lengths = _spec(one_chip, len_shape, jnp.int32)
    if kind == "contiguous":
        kc = _spec(one_chip, (SLOTS, CAP, KV, HD), jnp.bfloat16)
        text = _compile(lambda q, k, v, ln: fused_contiguous_attention(
            q, k, ln, v_cache=v), q, kc, kc, lengths)
    else:
        ccfg = CacheConfig(kind=kind, page_size=PAGE).sized(capacity=CAP,
                                                            slots=SLOTS)
        pool = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            jax.eval_shape(lambda: make_gqa_page_pool(
                                ccfg, KV, HD)))
        bt = _spec(one_chip, (SLOTS, ccfg.max_pages_per_seq), jnp.int32)
        fn = functools.partial(
            fused_paged_attention, page_size=PAGE,
            kv_scheme=ccfg.kv_scheme if ccfg.quantized else None)
        text = _compile(fn, q, pool, lengths, bt)
    assert "tpu_custom_call" in text
