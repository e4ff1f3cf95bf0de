"""Tensor-parallel sharded serving: sharded streams == single-device streams.

The house invariant for the TP engine (`ServeEngine(mesh=...)`): a paged
engine step sharded over the model axis — serving weight layout
(`sharding.params_shardings(serve_n_shard=True)`), head-sharded page pools
(`sharding.pool_shardings` + the shard_map wrap in `models.transformer`),
replicated residual/logits pins — emits BIT-IDENTICAL token streams to the
tp=1 engine, across cache formats, chunk widths and sampling/speculative
epilogues. The reduced qwen2 geometry is tp-invariant for tp in {1, 2}
(heads/kv/vocab all divide), so ONE params tree drives both engines and any
stream drift is a real numerics change, not a shape artifact.

Everything multi-device runs in a `run_py` subprocess (fresh python with
XLA_FLAGS=--xla_force_host_platform_device_count set before jax init —
same machinery as tests/test_distributed.py), so these tests exercise the
step's `jax.shard_map` regions on a real multi-device mesh.

Also pinned here:
  * the host side is device-count-agnostic: page ids (block-table rows),
    prefix-cache hits and allocator stats are identical under any mesh
  * zero cross-device KV-page movement: no collective in the lowered tp=2
    step touches an operand with the pool's (num_pages, page_size) dims
  * the per-device accounting: `kv_bytes_per_token` and the cost-model KV
    floors scale as 1/tp on a head-sharded mesh (`obs.cost` kv_shards)
  * autotune keys: plans are keyed on local kv-head count + VMEM budget
"""

import textwrap

import pytest

from test_distributed import run_py

# Subprocess preamble shared by every multi-device test: a reduced qwen2
# engine factory driving a fixed two-request workload. The SAME f32 params
# tree (quantized identically inside each engine) feeds tp=1 and tp>1.
PREAMBLE = """
import jax, numpy as np
from repro.cache import CacheConfig, prefix_page_hashes
from repro.launch.engine import ServeEngine
from repro.launch.mesh import make_driver_mesh, make_serving_mesh, make_test_mesh
from repro.launch.sampling import SamplingParams
from repro.configs import get_config
from repro.models import init_params

CFG = get_config('qwen2-7b').reduced()
PARAMS = init_params(jax.random.PRNGKey(0), CFG, tp=1)

def make_engine(mesh, scheme, kind, chunk, k=0):
    return ServeEngine('qwen2-7b', reduced=True, scheme=scheme,
                       slots=2, capacity=32,
                       cache_config=CacheConfig(kind=kind, page_size=8),
                       prefill_chunk=chunk, speculate_k=k, mesh=mesh,
                       params=jax.tree.map(lambda x: x, PARAMS), seed=0)

def drive(eng, mode):
    samp = None
    if mode == 'sampled':
        samp = SamplingParams(temperature=0.8, top_p=0.9, seed=123)
    eng.submit([3, 5, 7], max_tokens=6, sampling=samp)
    eng.submit([3, 5, 11, 13, 2, 9], max_tokens=6, sampling=samp)
    st = eng.run()
    toks = [list(map(int, r.tokens)) for r in eng.finished]
    return toks, st

def assert_stream_equal(cell, a, b):
    (t1, s1), (t2, s2) = a, b
    assert t1 == t2, f'{cell}: token streams differ\\n tp1={t1}\\n tp2={t2}'
    for key in ('ticks', 'ttft_ticks_p50', 'latency_ticks_p50'):
        assert s1[key] == s2[key], (cell, key, s1[key], s2[key])
"""


def _run(body, **kw):
    """run_py with the shared preamble; dedents `body` here because the
    concatenation with the flush-left PREAMBLE defeats run_py's dedent."""
    return run_py(PREAMBLE + textwrap.dedent(body), **kw)


def test_sharded_streams_bit_identical_fast():
    """Representative cells of the equivalence grid on a (1, 2) mesh —
    both cache formats, both chunk widths, all three epilogues. One
    subprocess amortizes jax startup across the cells; the FULL
    {format} x {chunk} x {mode} grid runs in the slow-marked test."""
    _run("""
    CELLS = [('fp16', 'paged_bf16', 1, 0, 'greedy'),
             ('fp5.33-e2m3', 'paged_ams', 4, 0, 'greedy'),
             ('fp5.33-e2m3', 'paged_ams', 4, 0, 'sampled'),
             ('fp5.33-e2m3', 'paged_ams', 4, 2, 'spec')]
    for scheme, kind, chunk, k, mode in CELLS:
        cell = f'{kind}/chunk{chunk}/{mode}'
        a = drive(make_engine(make_driver_mesh('none'), scheme, kind, chunk, k), mode)
        b = drive(make_engine(make_serving_mesh(2), scheme, kind, chunk, k), mode)
        assert_stream_equal(cell, a, b)
        print('ok', cell)
    """, devices=2, timeout=600)


@pytest.mark.slow
def test_sharded_streams_bit_identical_full_grid():
    """The full house-invariant grid: {paged_bf16, paged_ams} x chunk
    {1, 4} x {greedy, seeded sampling, speculative k=2} — every cell's
    sharded stream bit-identical to single-device."""
    _run("""
    for kind, scheme in [('paged_bf16', 'fp16'), ('paged_ams', 'fp5.33-e2m3')]:
        for chunk in (1, 4):
            for mode, k in [('greedy', 0), ('sampled', 0), ('spec', 2)]:
                cell = f'{kind}/chunk{chunk}/{mode}'
                a = drive(make_engine(make_driver_mesh('none'), scheme, kind,
                                      chunk, k), mode)
                b = drive(make_engine(make_serving_mesh(2), scheme, kind,
                                      chunk, k), mode)
                assert_stream_equal(cell, a, b)
                print('ok', cell)
    """, devices=2, timeout=1200)


def test_allocator_and_prefix_cache_mesh_invariant():
    """Page ids and prefix-cache behavior are head-dimension-free: the
    SAME shared-prefix workload (second request submitted after the first
    drains, so its two full prefix pages hit the published index) on
    (1,1), (1,2) and (2,2) meshes produces identical token streams,
    block-table rows and allocator stats — the scheduler, PageAllocator
    and prefix index never see the device count."""
    _run("""
    shared = [3, 5, 7, 11, 13, 2, 9, 4] * 2       # two full 8-token pages
    def drive_shared(mesh):
        eng = make_engine(mesh, 'fp5.33-e2m3', 'paged_ams', 4)
        eng.submit(shared + [17], max_tokens=4)
        eng.run()                                  # publish prefix pages
        eng.submit(shared + [19], max_tokens=4)    # warm: 2-page prefix hit
        eng.run()
        toks = [list(map(int, r.tokens)) for r in eng.finished]
        return (toks, eng.block_tables.tolist(), eng.alloc.stats())

    base = drive_shared(make_driver_mesh('none'))
    hashes = prefix_page_hashes(np.asarray(shared, np.int32), 8)
    assert base[2]['prefix_hit_pages'] == len(hashes) == 2
    for shape in [(1, 2), (2, 2)]:
        got = drive_shared(make_test_mesh(shape))
        assert got == base, (shape, got, base)
    print('allocator/prefix-cache identical under', [(1,1), (1,2), (2,2)])
    """, devices=8, timeout=600)


def test_no_kv_page_collectives_in_lowered_step():
    """HLO inspection of the compiled tp=2 step: activation all-gathers
    are expected (the bit-exact layout trades one tiny gather per linear
    for never splitting a contraction), but NO collective may touch an
    operand shaped like the page pool — pages are written, truncated and
    attended device-local, never gathered or resharded."""
    _run("""
    import re
    eng = make_engine(make_serving_mesh(2), 'fp5.33-e2m3', 'paged_ams', 4, 2)
    txt = eng._step.lower(*eng._step_shapes.values()).compile().as_text()
    ccfg = eng.cache_cfg
    pagedims = f'{ccfg.num_pages},{ccfg.page_size},'
    coll = [ln for ln in txt.splitlines()
            if re.search(r'all-gather|all-to-all|collective-permute', ln)]
    assert coll, 'expected activation collectives in a tp=2 step'
    bad = [ln for ln in coll if pagedims in ln]
    assert not bad, 'KV pages crossed the mesh:\\n' + '\\n'.join(bad[:4])
    print(f'{len(coll)} collectives, none touching ({ccfg.num_pages}, '
          f'{ccfg.page_size}) pool operands')
    """, devices=2, timeout=600)


def test_per_device_kv_bytes_scale_as_inverse_tp():
    """`kv_bytes_per_token` and the cost-model KV floors are per-device:
    a head-sharded tp=2 pool holds half the bytes per token per device,
    and `kv_floor_ratio` stays 1.0 because achieved and floor divide by
    the same shard count."""
    _run("""
    e1 = make_engine(make_driver_mesh('none'), 'fp5.33-e2m3', 'paged_ams', 4)
    e2 = make_engine(make_serving_mesh(2), 'fp5.33-e2m3', 'paged_ams', 4)
    assert e2.kv_bytes_per_token() * 2 == e1.kv_bytes_per_token()
    for f in ('kv_bytes_per_token', 'kv_ideal_bytes_per_token',
              'kv_bf16_bytes_per_token', 'kv_dequant_bytes_per_token'):
        assert getattr(e2.cost_model, f) * 2 == getattr(e1.cost_model, f), f
    assert e2.cost_model.weight_bytes * 2 == e1.cost_model.weight_bytes
    assert e1.signature['tp'] == 1 and e2.signature['tp'] == 2
    # compression ratio is per-device over per-device: tp-invariant
    assert e1.kv_compression_vs_bf16() == e2.kv_compression_vs_bf16()
    _, st = drive(e2, 'greedy')
    assert abs(st['kv_floor_ratio'] - 1.0) < 1e-9, st['kv_floor_ratio']
    print('per-device kv accounting scales 1/tp; floor ratio', st['kv_floor_ratio'])
    """, devices=2, timeout=600)


# ------------------------------------------------------- host-side (no mesh)
def test_cost_model_kv_shards():
    """`build_cost_model(kv_shards=...)` divides every KV floor (the
    per-device view) and rejects non-divisible head counts."""
    from repro.cache import CacheConfig
    from repro.configs import get_config
    from repro.obs import build_cost_model

    cfg = get_config("qwen2-7b").reduced()
    ccfg = CacheConfig(kind="paged_ams", page_size=8).sized(capacity=32, slots=2)
    full = build_cost_model(cfg, "fp5.33-e2m3", ccfg, kv=2, hd=32)
    half = build_cost_model(cfg, "fp5.33-e2m3", ccfg, kv=2, hd=32, kv_shards=2)
    for f in ("kv_bytes_per_token", "kv_ideal_bytes_per_token",
              "kv_bf16_bytes_per_token", "kv_dequant_bytes_per_token"):
        assert getattr(half, f) * 2 == getattr(full, f), f
    # weight/flop terms are governed by tp, not kv_shards
    assert half.weight_bytes == full.weight_bytes
    assert half.flops_per_token == full.flops_per_token
    with pytest.raises(ValueError):
        build_cost_model(cfg, "fp5.33-e2m3", ccfg, kv=3, hd=32, kv_shards=2)


def test_attn_plan_key_local_heads_and_budget():
    """A plan tuned at one kv-head count / VMEM budget is never served for
    another: both join the autotune key, so tp=1 and tp=4 head slices of
    the same cache shape plan independently."""
    from repro.kernels.tuning import (
        VMEM_BYTES,
        AutotuneCache,
        attn_plan_key,
        plan_attention_tiles,
    )

    kw = dict(kind="contiguous", family="gqa", scheme=None, rows=8, hd=32,
              hd_v=32, s_max=64)
    k_full = attn_plan_key(page=0, kv_heads=8, **kw)
    k_slice = attn_plan_key(page=0, kv_heads=2, **kw)
    k_budget = attn_plan_key(page=0, kv_heads=8, budget=VMEM_BYTES // 2, **kw)
    assert len({k_full, k_slice, k_budget}) == 3
    cache = AutotuneCache()
    plan_attention_tiles(cache=cache, kv_heads=8, **kw)
    assert cache.get(k_full) is not None and cache.get(k_slice) is None
    plan_attention_tiles(cache=cache, kv_heads=2, **kw)
    assert len(cache) == 2                      # distinct entries, no reuse
