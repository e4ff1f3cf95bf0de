"""The one traffic generator: it reads a mix's data file and yields requests.

A mix file (``traffic/<name>.json``) gives the prompt and output length
distributions, the closed loop's clients and the engine sizes the mix needs
(slots, capacity, pages). A seed changes what is asked, not how much work it
is: sizes are the distribution's quantiles at (i + 0.5) / n, paired
prompt-to-output by a fixed permutation and handed out in the same order to
every seed; the seed draws only the token ids.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List

import numpy as np
from scipy import stats

from model import BENCH

PAIRING_SEED = 0x5EED     # fixes how prompt and output quantiles pair up


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        tr = json.load(f)
    tr["name"] = name
    return tr


def rehearsal(tr: dict) -> dict:
    """The mix at rehearsal size: its ``rehearsal`` block overrides keys."""
    out = json.loads(json.dumps(tr))
    for k, v in tr.get("rehearsal", {}).items():
        out[k] = v
    return out


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n sizes at the quantiles (i + 0.5) / n of ``spec``, as integers."""
    q = (np.arange(n) + 0.5) / n
    d = spec["dist"]
    if d == "lognormal":
        v = stats.lognorm.ppf(q, spec["sigma"], scale=spec["median"])
    elif d == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown size distribution {d!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray        # int32 token ids
    max_tokens: int


class Mix:
    """Requests of one closed-loop mix for one seed: `next_closed()` hands
    them out in blocks of ``block``, each block the same (prompt, output)
    sizes in the same order."""

    def __init__(self, tr: dict, seed: int, vocab: int):
        self.tr = tr
        self.vocab = vocab
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
        self._pending: List[Request] = []

    def _block(self, n: int) -> List[Request]:
        p = quantiles(self.tr["prompt"], n)
        o = quantiles(self.tr["output"], n)
        p = p[np.random.default_rng(PAIRING_SEED).permutation(n)]
        o = o[np.random.default_rng(PAIRING_SEED + 1).permutation(n)]
        return [Request(self.rng.integers(0, self.vocab, int(p[i]),
                                          dtype=np.int32), int(o[i]))
                for i in range(n)]

    def next_closed(self) -> Request:
        if not self._pending:
            self._pending = self._block(self.tr["block"])
        return self._pending.pop(0)
