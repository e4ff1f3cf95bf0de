"""The generator: a seed changes the tokens, not the work."""

import numpy as np
import pytest

import traffic


def sizes(reqs):
    return [(len(r.prompt), r.max_tokens) for r in reqs]


@pytest.mark.parametrize("rehearse", [False, True])
def test_closed_loop_gives_every_seed_the_same_sizes_in_order(rehearse):
    tr = traffic.load_traffic("decode")
    if rehearse:
        tr = traffic.rehearsal(tr)
    a, b = (traffic.Mix(tr, seed, 151936) for seed in (1, 2**33 + 5))
    ra = [a.next_closed() for _ in range(3 * tr["block"])]
    rb = [b.next_closed() for _ in range(3 * tr["block"])]
    assert sizes(ra) == sizes(rb)
    assert not np.array_equal(ra[0].prompt, rb[0].prompt)
    for r in ra:
        assert len(r.prompt) + r.max_tokens - 1 <= tr["capacity"]


def test_quantiles_stay_in_range():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64,
            "max": 768}
    q = traffic.quantiles(spec, 32)
    assert q.min() >= 64 and q.max() <= 768 and np.all(np.diff(q) >= 0)
    assert abs(np.median(q) - 256) <= 16
