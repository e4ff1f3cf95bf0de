"""Useful work of an engine tick, counted from the configuration and from
what the tick fed: tokens, and for each fed query the keys it attends.

Counted this way the same work reads the same whatever implements it: the
program's padding (rows of the fixed [slots, chunk] step, K padded to a
packing block, pages past a sequence's end) is never counted, so dropping
it reads as a higher share of the roofline, never as more than 100%.

The KV vector layout is copied from the program's `obs/cost.py`
(`kv_vector_bytes_floor`), which a test holds equal to the pool's own
`pool_bytes_per_token`; here it is part of the yardstick.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict

import numpy as np

from model import BENCH, Model
from reference import parse_scheme


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in the table is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def effective_bits(scheme: str) -> float:
    E, M, k = parse_scheme(scheme)
    return (E + M) + (1.0 / k if k > 1 else 1.0)


def packed_weight_bytes(K: int, N: int, scheme: str) -> float:
    """Bytes of a true K x N packed weight: its codes at the scheme's
    effective bits, and one float32 scale per output channel."""
    return K * N * effective_bits(scheme) / 8.0 + 4.0 * N


def kv_vector_bytes(hd: int, scheme: str) -> int:
    """Bytes of one packed K or V vector: hi codes byte-packed over the head
    dim padded to lcm(k, 2), one shared LSB per k-group in 32-bit words,
    one float32 scale."""
    E, M, k = parse_scheme(scheme)
    unit = math.lcm(k, 2)
    hd_p = -(-hd // unit) * unit
    hi = -(-hd_p * (E + M) // 8)
    lsb = 4 * (-(-(hd_p // k) // 32))
    return hi + lsb + 4


def kv_bytes_per_token(m: Model) -> int:
    """One token's K and V in one layer, every kv head."""
    return 2 * m.n_kv * kv_vector_bytes(m.head_dim, m.kv)


def linear_shapes(m: Model):
    """(K, N) of each packed linear of one layer."""
    D, Hd, KVd, F = m.d_model, m.n_heads * m.head_dim, m.n_kv * m.head_dim, m.d_ff
    return [(D, Hd), (D, KVd), (D, KVd), (Hd, D), (D, F), (D, F), (F, D)]


def layer_weight_bytes(m: Model) -> float:
    return sum(packed_weight_bytes(K, N, m.weights) for K, N in linear_shapes(m))


def tick_work(m: Model, fed: np.ndarray, nvalid: np.ndarray,
              peak: Dict[str, float]) -> Dict[str, float]:
    """One chip's share of the FLOPs, bytes and least times of one tick,
    from each slot's start position ``fed`` and tokens fed ``nvalid`` (0 =
    idle slot): the whole model's divided by ``m.chips``. The linears, the
    attention and the LM head are split over the chips by heads and output
    channels, so an even split is the share a roofline is held to.

    * linears (``ams_matmul``): 2 x tokens x K x N FLOPs; bytes are the
      packed weight plus the fed tokens' bf16 inputs and outputs;
    * attention: 4 x heads x head_dim FLOPs per (query, key) pair, query j
      of a slot fed from position i attending i + j + 1 keys; bytes are the
      packed K and V of every key the slot's queries attend, read once, and
      the queries' bf16 inputs and outputs;
    * the step adds the LM head, 2 x D x V per active slot.
    Least time is max(FLOPs / peak FLOP/s, bytes / peak bytes/s), per layer
    and per linear, summed. ``tokens`` is the whole tick's."""
    P, BW = peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]
    fed = np.asarray(fed, np.int64)
    nvalid = np.asarray(nvalid, np.int64)
    act = nvalid > 0
    T = int(nvalid.sum())
    L = m.n_layers
    lin_f = lin_b = lin_t = 0.0
    for K, N in linear_shapes(m):
        f = 2.0 * T * K * N
        b = packed_weight_bytes(K, N, m.weights) + 2.0 * T * (K + N)
        lin_f += f
        lin_b += b
        lin_t += max(f / P, b / BW)
    i, n = fed[act], nvalid[act]
    pairs = float(np.sum(n * i + n * (n + 1) // 2))
    keys = float(np.sum(i + n))
    Hhd = m.n_heads * m.head_dim
    att_f = 4.0 * Hhd * pairs
    att_b = keys * kv_bytes_per_token(m) + 4.0 * T * Hhd
    head_f = 2.0 * int(act.sum()) * m.d_model * m.vocab
    whole = {
        "matmul_flops": L * lin_f, "matmul_bytes": L * lin_b,
        "matmul_least_s": L * lin_t,
        "attn_flops": L * att_f, "attn_bytes": L * att_b,
        "attn_least_s": L * max(att_f / P, att_b / BW),
        "step_flops": L * (lin_f + att_f) + head_f,
    }
    return {"tokens": float(T),
            **{k: v / m.chips for k, v in whole.items()}}


def sum_work(m: Model, ticks, peak) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for t in ticks:
        for k, v in tick_work(m, t.fed, t.nvalid, peak).items():
            out[k] = out.get(k, 0.0) + v
    return out
