"""The work counts against the program's own layouts, at rehearsal size
(packed tree) and at every configuration's published size (KV bytes)."""

import jax
import pytest

import system
import work
from model import load_model

CONFIGS = ("qwen2-7b-ams5",)


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", CONFIGS)
def test_kv_bytes_match_pool(name, rehearse):
    from repro.cache import CacheConfig
    from repro.cache.pool import pool_bytes_per_token
    m = load_model(name, rehearse=rehearse)
    ccfg = CacheConfig(kind="paged_ams", kv_scheme=m.kv)
    assert work.kv_bytes_per_token(m) == pool_bytes_per_token(
        m.n_kv, m.head_dim, ccfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_bytes_match_packed_tree(name):
    """Each packed linear of the built tree holds exactly the scheme's
    effective bits per weight over K padded to the packing block, plus a
    float32 scale per column; the work count is the same at the true K."""
    from repro.core.formats import get_scheme
    from repro.core.packing import make_layout
    m = load_model(name, rehearse=True)
    params = system.build_params(m, system.program_config(m), seed=5)
    lay = make_layout(get_scheme(m.weights))
    attn = params["layers"]["sub0"]["attn"]
    ffn = params["layers"]["sub0"]["ffn"]
    built = [attn["wq"], attn["wk"], attn["wv"], attn["wo"],
             ffn["w_gate"], ffn["w_up"], ffn["w_down"]]
    for (K, N), p in zip(work.linear_shapes(m), built):
        nbytes = sum(p[k].nbytes for k in ("hi", "lsb", "scale")) / m.n_layers
        assert nbytes == pytest.approx(
            work.packed_weight_bytes(lay.padded_k(K), N, m.weights), rel=1e-12)
        assert work.packed_weight_bytes(K, N, m.weights) <= nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(
        [params["layers"]["sub0"][b] for b in ("attn", "ffn")])
        if x.dtype != jax.numpy.bfloat16)
    assert total >= m.n_layers * work.layer_weight_bytes(m)


def test_tick_work_counts_only_fed_work():
    m = load_model("qwen2-7b-ams5")
    peak = work.peaks("TPU v5 lite")
    import numpy as np
    fed = np.array([100, 0, 7, 0])
    nvalid = np.array([1, 0, 16, 0])       # one decode, one prefill chunk
    w = work.tick_work(m, fed, nvalid, peak)
    assert w["tokens"] == 17
    pairs = 101 + sum(7 + j + 1 for j in range(16))
    assert w["attn_flops"] == 4 * 28 * 128 * pairs * 28
    assert w["attn_bytes"] == 28 * ((101 + 23) * work.kv_bytes_per_token(m)
                                    + 4 * 17 * 28 * 128)
    assert w["step_flops"] > w["matmul_flops"] + w["attn_flops"]
    assert work.tick_work(m, fed, np.zeros(4, int), peak)["step_flops"] == 0


# tick_work of qwen2-7b-ams5 on the feed of test_tick_work_counts_only_fed_work,
# as it read before it gave one chip's share
PINNED = {"tokens": 17.0, "matmul_flops": 221859807232.0,
          "matmul_bytes": 4441541290.666666,
          "matmul_least_s": 0.005423127339031339,
          "attn_flops": 140091392.0, "attn_bytes": 8823808.0,
          "attn_least_s": 1.0773880341880342e-05,
          "step_flops": 224179888128.0}


def test_tick_work_is_one_chips_share():
    """One chip reads as before; on four, each chip's least times, FLOPs
    and bytes are a quarter of the whole model's, and the tokens stay the
    tick's."""
    import dataclasses
    import numpy as np
    m = load_model("qwen2-7b-ams5")
    assert m.chips == 1
    peak = work.peaks("TPU v5 lite")
    fed, nvalid = np.array([100, 0, 7, 0]), np.array([1, 0, 16, 0])
    one = work.tick_work(m, fed, nvalid, peak)
    assert one == PINNED
    four = work.tick_work(dataclasses.replace(m, chips=4), fed, nvalid, peak)
    assert four["tokens"] == one["tokens"]
    for k in PINNED.keys() - {"tokens"}:
        assert four[k] == one[k] / 4, k


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        work.peaks("TPU v9 imaginary")
