"""The fused attention template (`repro.kernels.attention_template`).

Contract: ONE grid/loop body serves every decode path, and every lowering
is pinned to the same oracle family —

  * ``impl="ref"`` IS `flash_decode`/`flash_decode_chunk` (bit-identical:
    `attend_contiguous` must return the very same arrays the pre-template
    cores computed), and unfusable cases (mesh collectives, ring/sliding
    window, non-group-major head maps) raise under a fused impl instead of
    silently running that path;
  * the fused Pallas lowering (interpret mode here) agrees with the XLA
    oracle to f32-reduction tolerance across the full
    {gqa, mla} x {contiguous, paged_bf16, paged_ams} x chunk {1, 4} grid,
    idle slots / masked ragged rows flushing to EXACT zeros;
  * the paged walk stops at each slot's length: pages past it are never
    read (a NaN tail leaves the output finite) and skipping them changes
    no bit against the contiguous template's walk over every block;
  * the whole engine decodes through the fused contiguous path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import CacheConfig, gather_kv, make_gqa_page_pool, paged_insert
from repro.kernels.attention_template import (
    attend_contiguous,
    flash_decode,
    flash_decode_chunk,
    fused_contiguous_attention,
    fused_paged_attention,
)
from repro.launch.engine import ServeEngine

B, KV, H, HD = 2, 2, 4, 32
R_KV = 16                      # MLA value slice of the compressed stream


# ------------------------------------------------------------------ fixtures
def _dense_case(family, chunk, seed=0, dtype=jnp.float32, S=16):
    """(q, k_cache, v_cache, lengths, kv_map, value_slice): slot 1 idle /
    mostly-masked so exact-zero rows are exercised in every cell."""
    rng = np.random.default_rng(seed)
    kv = 1 if family == "mla" else KV
    k = jnp.asarray(rng.standard_normal((B, S, kv, HD)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((B, S, kv, HD)), dtype=dtype)
    if chunk == 1:
        q = jnp.asarray(rng.standard_normal((B, H, HD)), dtype=dtype)
        lengths = jnp.asarray([13, 0], jnp.int32)          # slot 1 idle
    else:
        q = jnp.asarray(rng.standard_normal((B, chunk, H, HD)), dtype=dtype)
        lengths = jnp.asarray([[10, 11, 12, 13], [7, 0, 0, 0]], jnp.int32)
    kvm = np.zeros(H, np.int32) if kv == 1 else np.arange(H) // (H // kv)
    vs = R_KV if family == "mla" else None
    return q, k, v, lengths, kvm, vs


def _oracle(q, k, v, lengths, kvm, vs, **kw):
    v = k[..., :vs] if vs is not None else v
    if q.ndim == 3:
        return flash_decode(q, k, v, lengths, kv_map=kvm, **kw)
    return flash_decode_chunk(q, k, v, lengths, kv_map=kvm, **kw)


def _filled_pool(ccfg, kv, hd, lens, seed=0):
    rng = np.random.default_rng(seed)
    pool = make_gqa_page_pool(ccfg, kv, hd)
    perm = rng.permutation(ccfg.num_pages)[: B * ccfg.max_pages_per_seq]
    bt = jnp.asarray(perm.reshape(B, -1).astype(np.int32))
    for t in range(max(lens)):
        k_new = jnp.asarray(rng.standard_normal((B, 1, kv, hd)), jnp.bfloat16)
        v_new = jnp.asarray(rng.standard_normal((B, 1, kv, hd)), jnp.bfloat16)
        pos = jnp.asarray(np.where(t < np.asarray(lens), t, -1), jnp.int32)
        pool = paged_insert(pool, k_new, v_new, pos, bt, ccfg)
    return pool, bt


# ------------------------------------------------- ref tier + dispatch rules
def test_ref_impl_is_flash_decode_bitwise():
    for chunk in (1, 4):
        q, k, v, lengths, kvm, _ = _dense_case("gqa", chunk)
        got = attend_contiguous(q, k, v, lengths, kv_map=kvm, impl="ref")
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_oracle(q, k, v, lengths, kvm, None)))


def test_unfusable_cases_fall_back_to_ref_bitwise():
    """window/ring, sequence-sharded and non-group-major cases have no fused
    lowering: a fused impl raises for them (no silent downgrade to XLA),
    and impl="ref" still serves them bit-identically to flash_decode."""
    q, k, v, lengths, kvm, _ = _dense_case("gqa", 1)
    scrambled = np.array([1, 0, 1, 0], np.int32)      # not group-major
    for impl in ("pallas", "pallas_interpret"):
        for kw in (dict(kv_map=kvm, window=4), dict(kv_map=kvm, ring=True),
                   dict(kv_map=kvm, axis_name="model"),
                   dict(kv_map=scrambled)):
            with pytest.raises(NotImplementedError, match="impl='ref'"):
                attend_contiguous(q, k, v, lengths, impl=impl, **kw)
    got = attend_contiguous(q, k, v, lengths, kv_map=kvm, impl="ref",
                            window=4)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(flash_decode(q, k, v, lengths, kv_map=kvm, window=4)))
    got = attend_contiguous(q, k, v, lengths, kv_map=scrambled, impl="ref")
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(flash_decode(q, k, v, lengths, kv_map=scrambled)))


def test_fused_contiguous_validation():
    q, k, v, lengths, kvm, _ = _dense_case("gqa", 1)
    with pytest.raises(ValueError, match="v_cache or value_slice"):
        fused_contiguous_attention(q, k, lengths, interpret=True)
    with pytest.raises(ValueError, match="divide"):
        fused_contiguous_attention(q, k, lengths, v_cache=v, block_kv=5,
                                   interpret=True)


def test_template_is_the_single_home():
    """models.attention and cache.paged_attention serve the template's own
    objects — the duplicated loop bodies are gone, not just unused."""
    from repro.cache import paged_attention as pa
    from repro.kernels import attention_template as tpl
    from repro.models import attention as A
    assert A.flash_decode is tpl.flash_decode
    assert A.flash_decode_chunk is tpl.flash_decode_chunk
    assert pa.online_softmax_step is tpl.online_softmax_step
    assert pa.restore_page is tpl.restore_page


# --------------------------------------- the fused grid, pinned to the oracle
@pytest.mark.slow
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("family", ["gqa", "mla"])
def test_fused_contiguous_matches_ref(family, chunk):
    q, k, v, lengths, kvm, vs = _dense_case(family, chunk)
    want = _oracle(q, k, v, lengths, kvm, vs)
    got = attend_contiguous(q, k, v if vs is None else k[..., :vs], lengths,
                            kv_map=kvm, impl="pallas_interpret",
                            value_slice=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-6)
    assert np.all(np.asarray(got)[1] == 0) == (chunk == 1)   # idle slot
    if chunk == 4:
        assert np.all(np.asarray(got)[1, 1:] == 0)   # masked ragged rows


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("family", ["gqa", "mla"])
@pytest.mark.parametrize("kind", ["paged_bf16", "paged_ams"])
def test_fused_paged_matches_ref(kind, family, chunk):
    """The paged lowerings against the gather -> (dequantize ->) attend
    oracle: same block-table walk, AMS planes restored to the exact
    lattice values the dense oracle dequantizes to."""
    ccfg = CacheConfig(kind=kind, page_size=4).sized(capacity=16, slots=B)
    kv = 1 if family == "mla" else KV
    lens = (13, 7) if chunk == 4 else (13, 0)
    pool, bt = _filled_pool(ccfg, kv, HD, lens)
    rng = np.random.default_rng(3)
    if chunk == 1:
        q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.float32)
        lengths = jnp.asarray(lens, jnp.int32)
    else:
        q = jnp.asarray(rng.standard_normal((B, chunk, H, HD)), jnp.float32)
        lengths = jnp.asarray([[10, 11, 12, 13], [7, 0, 0, 0]], jnp.int32)
    kvm = np.zeros(H, np.int32) if kv == 1 else np.arange(H) // (H // kv)
    vs = R_KV if family == "mla" else None
    # oracle attends the dense gathered view in the dtype the fused path
    # computes in: restored-f32 lattice values for AMS, bf16 pages else
    dtype = jnp.float32 if ccfg.quantized else jnp.bfloat16
    kd, vd = gather_kv(pool, bt, HD, ccfg, dtype=dtype)
    want = _oracle(q, kd, vd, lengths, kvm, vs)
    got = fused_paged_attention(
        q, pool, lengths, bt, page_size=ccfg.page_size,
        kv_scheme=ccfg.kv_scheme if ccfg.quantized else None,
        value_slice=vs, interpret=True)
    # AMS restores f32 lattice values -> f32-reduction tolerance; bf16
    # pages round p to bf16 at the RUNNING max (oracle: the global max),
    # so those cells agree only to bf16 precision
    atol, rtol = (2e-6, 1e-6) if ccfg.quantized else (2e-3, 2e-2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=rtol)
    if chunk == 1:
        assert np.all(np.asarray(got)[1] == 0)       # idle slot: exact zeros
    else:
        assert np.all(np.asarray(got)[1, 1:] == 0)   # masked ragged rows


# ----------------------------------- the paged walk stops at the slot length
PAGE, WIDTH = 4, 4                    # 16 keys per slot, 4 pages a row
TAIL_LENGTHS = {   # slot 0 long, slot 1 short (chunk 4: ragged rows), 2 idle
    1: [13, 6, 0],
    4: [[10, 11, 12, 13], [6, 0, 0, 0], [0, 0, 0, 0]],
}


def _owned_pool(kind, lengths, seed=0):
    """(ccfg, pool, owned [S, WIDTH] page ids, npg [S]): a 3-slot pool whose
    slots own distinct pages, written at every position of their OWNED
    pages (the ones their longest query row reaches); no other page is
    written."""
    S = len(lengths)
    ccfg = CacheConfig(kind=kind, page_size=PAGE, num_pages=4 * S * WIDTH
                       ).sized(capacity=PAGE * WIDTH, slots=S)
    rng = np.random.default_rng(seed)
    top = np.asarray(lengths).reshape(S, -1).max(axis=1)
    npg = -(-top // PAGE)
    owned = rng.permutation(ccfg.num_pages)[: S * WIDTH].reshape(S, WIDTH)
    pool = make_gqa_page_pool(ccfg, KV, HD)
    bt = jnp.asarray(owned.astype(np.int32))
    for t in range(PAGE * WIDTH):
        k_new = jnp.asarray(rng.standard_normal((S, 1, KV, HD)), jnp.bfloat16)
        v_new = jnp.asarray(rng.standard_normal((S, 1, KV, HD)), jnp.bfloat16)
        pos = jnp.asarray(np.where(t < npg * PAGE, t, -1), jnp.int32)
        pool = paged_insert(pool, k_new, v_new, pos, bt, ccfg)
    return ccfg, pool, owned, npg


def _query(lengths, seed=3):
    rng = np.random.default_rng(seed)
    S = len(lengths)
    chunked = np.ndim(lengths) == 2
    shape = (S, len(lengths[0]), H, HD) if chunked else (S, H, HD)
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(lengths, jnp.int32))


def _fused(q, pool, lengths, bt, ccfg):
    return np.asarray(fused_paged_attention(
        q, pool, lengths, jnp.asarray(bt, jnp.int32), page_size=PAGE,
        kv_scheme=ccfg.kv_scheme if ccfg.quantized else None,
        interpret=True))


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("kind", ["paged_bf16", "paged_ams"])
def test_fused_paged_never_reads_past_length(kind, chunk):
    """Every page no slot owns is NaN and every row's tail points at those
    pages, a different one per step: the kernel must never attend them,
    so its output stays finite and matches the oracle on the clean pool;
    the idle slot and the masked ragged rows stay exact zeros."""
    lengths = TAIL_LENGTHS[chunk]
    ccfg, pool, owned, npg = _owned_pool(kind, lengths)
    S = len(lengths)
    mine = np.zeros(ccfg.num_pages, bool)
    for s in range(S):
        mine[owned[s, :npg[s]]] = True
    spare = iter(np.flatnonzero(~mine))
    bt = owned.copy()
    for s in range(S):
        for i in range(npg[s], WIDTH):
            bt[s, i] = next(spare)
    dirty = jnp.asarray(~mine)[:, None, None, None]

    def poison(a):
        if jnp.issubdtype(a.dtype, jnp.floating):
            return jnp.where(dirty, jnp.asarray(jnp.nan, a.dtype), a)
        return jnp.where(dirty, jnp.asarray(-1, a.dtype), a)

    nan_pool = jax.tree.map(poison, pool)
    q, ln = _query(lengths)
    got = _fused(q, nan_pool, ln, bt, ccfg)
    assert np.all(np.isfinite(got))
    dtype = jnp.float32 if ccfg.quantized else jnp.bfloat16
    kd, vd = gather_kv(pool, jnp.asarray(bt), HD, ccfg, dtype=dtype)
    kvm = np.arange(H) // (H // KV)
    want = np.asarray(_oracle(q, kd, vd, ln, kvm, None))
    atol, rtol = (2e-6, 1e-6) if ccfg.quantized else (2e-3, 2e-2)
    np.testing.assert_allclose(got, want, atol=atol, rtol=rtol)
    assert np.all(got[2] == 0)                       # idle slot
    if chunk == 4:
        assert np.all(got[1, 1:] == 0)               # masked ragged rows


@pytest.mark.slow
@pytest.mark.parametrize("full", [False, True], ids=["ragged", "full"])
@pytest.mark.parametrize("kind", ["paged_bf16", "paged_ams"])
def test_fused_paged_tail_is_bitwise_irrelevant(kind, full):
    """The same pool and lengths through a 0-padded tail and through a tail
    of other slots' real pages give the same bits, and both equal the
    contiguous template's walk over EVERY block of the gathered rows (the
    walk that attends the masked pages): stopping at the length is a pure
    cut of work. At full length every slot attends its whole row."""
    lengths = [16, 16, 16] if full else TAIL_LENGTHS[1]
    ccfg, pool, owned, npg = _owned_pool(kind, lengths)
    padded = np.where(np.arange(WIDTH)[None] < npg[:, None], owned, 0)
    foreign = np.where(np.arange(WIDTH)[None] < npg[:, None], owned,
                       np.roll(owned, 1, axis=0))
    q, ln = _query(lengths)
    got = _fused(q, pool, ln, padded, ccfg)
    np.testing.assert_array_equal(got, _fused(q, pool, ln, foreign, ccfg))
    dtype = jnp.float32 if ccfg.quantized else jnp.bfloat16
    kd, vd = gather_kv(pool, jnp.asarray(padded), HD, ccfg, dtype=dtype)
    whole = fused_contiguous_attention(q, kd, ln, v_cache=vd,
                                       block_kv=PAGE, interpret=True)
    np.testing.assert_array_equal(got, np.asarray(whole))
    assert np.all(got[2] == 0) != full               # idle slot


# ------------------------------------------------------- engine end-to-end
@pytest.mark.slow
def test_contiguous_engine_fused_end_to_end():
    """The CONTIGUOUS engine decodes through the fused template
    (CacheConfig(impl=...) now threads to the GQA cores): the workload
    completes and the step signature advertises the lowering."""
    rng = np.random.default_rng(7)
    work = [(rng.integers(0, 512, 5), 3), (rng.integers(0, 512, 3), 4)]
    eng = ServeEngine("qwen2-7b", scheme="fp5.33-e2m3", slots=2, capacity=16,
                      seed=0,
                      cache_config=CacheConfig(impl="pallas_interpret"))
    assert eng.signature["impl"] == "pallas_interpret"
    reqs = [eng.submit(p, mt) for p, mt in work]
    eng.run()
    assert [len(r.tokens) for r in reqs] == [mt for _, mt in work]
