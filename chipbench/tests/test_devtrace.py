"""The trace reduction, on a hand-made trace and on one recorded on a v5e."""

import gzip
import importlib.util
import json
import os

import pytest

import devtrace
from devtrace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def small():
    return Trace(
        ops={0: [("%while.3", 0.0, 4.0), ("%fusion.1", 0.0, 1.0),
                 ("%fusion.2", 1.0, 2.0), ("%kernel.7 tpu_custom_call", 3.0, 4.0)]},
        modules={0: [("jit_engine_fn", 0.0, 4.0)]},
        host=[("step_begin", 0.0, 0.2), ("step_end", 0.2, 4.0),
              ("collect", 4.0, 4.5)])


def test_union_merges_overlaps():
    assert devtrace.union(devtrace.leaves(small().ops[0])) == [(0.0, 2.0),
                                                               (3.0, 4.0)]
    assert devtrace.union(small().ops[0]) == [(0.0, 4.0)]


def test_window_busy_and_gaps():
    tr = small()
    assert devtrace.window(tr) == (0.0, 4.5)
    assert devtrace.busy_seconds(tr) == pytest.approx(4.0)
    assert devtrace.idle_gaps(tr) == [["collect", 0.5]]


def test_kernels_modules_and_top_ops():
    tr = small()
    assert devtrace.kernel_seconds(tr, [r"tpu_custom_call$"]) == pytest.approx(1.0)
    assert devtrace.kernel_seconds(tr, [r"no_such_kernel"]) is None
    assert devtrace.module_runs(tr, r"engine_fn") == 1
    assert devtrace.top_ops(tr) == [["%fusion", 2.0],
                                    ["%kernel tpu_custom_call", 1.0]]


def test_json_round_trip():
    tr = small()
    back = Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back == tr



def recorded():
    """One tick of qwen2-7b-ams5.decode traced on a TPU v5 lite, as
    `devtrace.load` reduced it (``run.py --trace 1 --save-trace``)."""
    with gzip.open(os.path.join(DATA, "decode_tick_v5e.json.gz"), "rt") as f:
        return Trace.from_json(json.load(f))


def kernel(metric):
    path = os.path.join(os.path.dirname(DATA), "..", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


def test_recorded_trace_reduces():
    tr = recorded()
    lo, hi = devtrace.window(tr)
    busy = devtrace.busy_seconds(tr)
    assert 0 < busy <= hi - lo
    assert devtrace.module_runs(tr, r"engine_fn") == 1
    ams = devtrace.kernel_seconds(tr, kernel("ams_matmul_roofline"))
    attn = devtrace.kernel_seconds(tr, kernel("paged_attn_roofline"))
    assert 0 < ams and 0 < attn and ams + attn <= busy
    top = dict(devtrace.top_ops(tr))
    assert "%while" not in top
    assert max(top, key=top.get) in {"%ams_matmul_padded tpu_custom_call",
                                     "%closed_call tpu_custom_call"}
    gaps = devtrace.idle_gaps(tr, n=10_000)
    assert all(name in devtrace.HOST_SPANS + ("host",) for name, _ in gaps)
    assert sum(s for _, s in gaps) == pytest.approx(hi - lo - busy, rel=1e-6)
