"""Block-shape selection + VMEM budgeting for the Pallas kernels.

Two planners live here:

  * `plan_tiles` — the AMS matmul (bB, bK, bN) tile. The dry-run has no
    wall clock, so tile choice is *structural*: pick the largest
    MXU-aligned (bK, bN) whose working set fits the VMEM budget with
    double-buffered input streams, preferring K-depth (amortizes the f32
    accumulator) over N-width. This is the reasoning the §Perf Pallas
    hints prescribe — from the lowered resource model, not a trace.
  * `plan_attention_tiles` — the KV block size of the fused attention
    template (`kernels.attention_template`), fronted by a PERSISTENT
    per-(shape, family, scheme) `AutotuneCache`. The default plan is
    deterministic (largest divisor of the cache length whose working set
    fits the budget — CI stays reproducible); pass a ``measure`` callable
    (plan -> seconds) to pick by wall clock instead, and the winner is
    persisted so later sessions reuse it. Set the
    ``REPRO_ATTN_AUTOTUNE_CACHE`` env var to a JSON path to persist
    across processes.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Callable, Dict, Optional

from repro.core.formats import get_scheme
from repro.core.kv_quant import packed_head_dim
from repro.core.packing import PackLayout

VMEM_BYTES = 16 * 2 ** 20  # v5e per-core VMEM


@dataclasses.dataclass(frozen=True)
class TilePlan:
    bb: int
    bk: int
    bn: int
    vmem_bytes: int
    pipeline_buffers: int = 2  # double buffering


# Unpack + restore working set of one hi-plane tile, per K row of the tile:
# ``row + col * bn`` bytes, keyed by codes per int32 word. It covers the
# double-buffered packed-plane DMAs, the per-segment shifts, the
# position-order stack/reshape relayout and the f32 restore — everything
# the kernel body holds that scales with bk. Fitted as an upper bound
# (3-4 % above) to the scoped VMEM the v5e compiler reports for
# ams_matmul at bb=8 over bk x bn in {one or two K blocks} x {128, 256,
# 512}, for every scheme in core.formats.SCHEMES. The kernel compiles
# with its VMEM limit set to `vmem_usage`, so an estimate that falls
# short fails the TPU compile tests instead of passing unnoticed.
_RESTORE_BYTES_PER_K_ROW = {4: (1000, 20.0), 5: (13300, 4.8),
                            6: (7200, 4.0), 8: (8300, 5.3)}


def vmem_usage(lay: PackLayout, bb: int, bk: int, bn: int,
               buffers: int = 2) -> int:
    """Bytes of VMEM a (bb, bk, bn) tile claims in ams_matmul."""
    row, col = _RESTORE_BYTES_PER_K_ROW[lay.per_word]
    x = 4 * bb * bk
    scale = 4 * bn
    streams = buffers * (x + scale)                    # double-buffered DMAs
    restore = int(bk * (row + col * bn))               # planes + restore
    acc = 4 * bb * bn                                  # f32 accumulator
    out = 4 * bb * bn
    return streams + restore + acc + out


def plan_tiles(lay: PackLayout, B: int, K: int, N: int,
               budget: int = VMEM_BYTES) -> TilePlan:
    """Largest aligned tile under budget; K-major growth."""
    bb = min(max(8, 1 << (B - 1).bit_length()), 128)
    base_k = math.lcm(lay.k_block, 128)
    best = None
    for bn in (512, 256, 128):
        for mult in (8, 6, 4, 3, 2, 1):
            bk = base_k * mult
            if bk > max(base_k, K * 2):
                continue
            use = vmem_usage(lay, bb, bk, bn)
            if use <= budget:
                cand = TilePlan(bb, bk, bn, use)
                if best is None or (cand.bk * cand.bn) > (best.bk * best.bn):
                    best = cand
        if best is not None:
            break
    if best is None:  # fall back to the minimum legal tile
        best = TilePlan(8, base_k, 128, vmem_usage(lay, 8, base_k, 128))
    return best


# ---------------------------------------------------------------------------
# Fused-attention KV-block planning (persistent autotune cache)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnTilePlan:
    """One KV-block choice for the fused attention template."""

    block_kv: int        # keys per grid step (page_size when paged)
    rows: int            # folded query rows (chunk * group) per cell
    vmem_bytes: int      # structural working-set estimate
    source: str = "default"   # default | measured | fallback | cache


def attn_vmem_usage(rows: int, block_kv: int, hd: int,
                    hd_v: Optional[int] = None, scheme: Optional[str] = None,
                    buffers: int = 2, heads: int = 1) -> int:
    """Bytes of VMEM one (rows, block_kv) attention cell claims: the
    double-buffered K/V streams (packed planes for an AMS scheme, else f32
    upper bound), the in-VREG restore tiles, q, the f32 accumulator and the
    (rows, 128) m/l scratch columns — for each of the ``heads`` kv heads a
    cell holds."""
    hd_v = hd if hd_v is None else hd_v
    if scheme is not None:
        fmt = get_scheme(scheme)
        hd_p = packed_head_dim(hd, fmt)
        gw = -(-(hd_p // fmt.k) // 32)
        plane = block_kv * (hd_p // 2) + 4 * block_kv * gw + 4 * block_kv
        streams = buffers * 2 * plane                  # K and V plane DMAs
        decoded = 4 * block_kv * (hd + hd_v)           # f32 restore tiles
    else:
        streams = buffers * 4 * block_kv * (hd + hd_v)
        decoded = 0
    q = 4 * rows * hd
    acc = 4 * rows * hd_v
    ml = 2 * 4 * rows * 128
    out = 4 * rows * hd_v
    return heads * (streams + decoded + q + acc + ml + out)


def attn_plan_key(*, kind: str, family: str, scheme: Optional[str],
                  rows: int, hd: int, hd_v: int, s_max: int,
                  page: int = 0, kv_heads: int = 0,
                  budget: int = VMEM_BYTES) -> str:
    """Canonical per-(shape, family, scheme) cache key.

    ``kv_heads`` is the LOCAL (per-shard) kv-head count of the launching
    grid and ``budget`` the VMEM budget the plan was selected under: a
    tensor-parallel engine hands each device a head SLICE of the cache, so
    a plan tuned at tp=1 (full heads, default budget) must never be
    silently served for a tp=4 slice — different grid height, different
    occupancy. 0 = unspecified (pre-sharding callers), kept distinct from
    any real count."""
    return (f"{kind}/{family}/{scheme or 'bf16'}/rows{rows}/hd{hd}"
            f"v{hd_v}/s{s_max}/p{page}/kv{kv_heads}/vb{budget}")


class AutotuneCache:
    """Persistent plan store: a dict keyed by `attn_plan_key`, mirrored to
    a JSON file when a path is given (load on construction, rewrite on
    every put). Plans round-trip exactly — `source` is stored so a
    measured plan stays marked measured after reload."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._plans: Dict[str, AttnTilePlan] = {}
        if path is not None and os.path.exists(path):
            self.load(path)

    def __len__(self) -> int:
        return len(self._plans)

    def get(self, key: str) -> Optional[AttnTilePlan]:
        return self._plans.get(key)

    def put(self, key: str, plan: AttnTilePlan) -> None:
        self._plans[key] = plan
        if self.path is not None:
            self.save(self.path)

    def load(self, path: str) -> None:
        with open(path) as f:
            raw = json.load(f)
        for k, d in raw.items():
            self._plans[k] = AttnTilePlan(**d)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({k: dataclasses.asdict(p)
                       for k, p in sorted(self._plans.items())}, f, indent=1)


_ATTN_CACHE: Optional[AutotuneCache] = None


def get_autotune_cache() -> AutotuneCache:
    """Process-wide cache; persists to $REPRO_ATTN_AUTOTUNE_CACHE if set."""
    global _ATTN_CACHE
    if _ATTN_CACHE is None:
        _ATTN_CACHE = AutotuneCache(os.environ.get("REPRO_ATTN_AUTOTUNE_CACHE"))
    return _ATTN_CACHE


def _divisors_desc(n: int):
    out = {n}
    for i in range(1, int(math.isqrt(n)) + 1):
        if n % i == 0:
            out.add(i)
            out.add(n // i)
    return sorted(out, reverse=True)


def plan_attention_tiles(*, kind: str, family: str, scheme: Optional[str],
                         rows: int, hd: int, hd_v: Optional[int] = None,
                         s_max: int, page: int = 0, kv_heads: int = 0,
                         budget: int = VMEM_BYTES,
                         cache: Optional[AutotuneCache] = None,
                         measure: Optional[Callable[[AttnTilePlan], float]]
                         = None) -> AttnTilePlan:
    """KV-block plan for one fused-attention shape.

    ``kind`` is "paged" (block fixed at ``page``) or "contiguous" (block
    chosen from the divisors of ``s_max`` — a block never reads past the
    cache). Deterministic default: the LARGEST candidate whose
    `attn_vmem_usage` fits ``budget``; none fitting falls back to the
    smallest divisor (marked ``source="fallback"``). A ``measure``
    callable re-ranks the fitting candidates by measured seconds
    (ties break to the larger block) and is never consulted on a cache
    hit already measured. Results persist via ``cache`` (defaults to the
    process-wide `get_autotune_cache`). ``kv_heads`` is the launching
    grid's LOCAL kv-head count (per-shard under tensor parallelism) and
    joins ``budget`` in the cache key — see `attn_plan_key`."""
    hd_v = hd if hd_v is None else hd_v
    cache = cache if cache is not None else get_autotune_cache()
    key = attn_plan_key(kind=kind, family=family, scheme=scheme, rows=rows,
                        hd=hd, hd_v=hd_v, s_max=s_max, page=page,
                        kv_heads=kv_heads, budget=budget)
    hit = cache.get(key)
    if hit is not None and (measure is None or hit.source == "measured"):
        return hit
    heads = max(kv_heads, 1)
    if kind == "paged":
        plan = AttnTilePlan(page, rows, attn_vmem_usage(
            rows, page, hd, hd_v, scheme, heads=heads))
        cache.put(key, plan)
        return plan
    cands = [AttnTilePlan(bk, rows, attn_vmem_usage(rows, bk, hd, hd_v,
                                                    scheme, heads=heads))
             for bk in _divisors_desc(s_max)]
    fitting = [p for p in cands if p.vmem_bytes <= budget]
    if not fitting:
        plan = dataclasses.replace(cands[-1], source="fallback")
    elif measure is not None:
        timed = [(measure(p), -p.block_kv, p) for p in fitting]
        plan = dataclasses.replace(min(timed)[2], source="measured")
    else:
        plan = fitting[0]                      # largest fitting block
    cache.put(key, plan)
    return plan
