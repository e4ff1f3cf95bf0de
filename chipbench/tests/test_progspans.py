"""The readers of the program's tick phases (`progspans`,
``host_ms_per_tick``, ``host_stall_ms_per_tick``), on a hand-made trace
with its span record and on ticks recorded on a v5e."""

import gzip
import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

import devtrace
import progspans
from devtrace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
MS = 1_000_000                           # ns
T0 = 1_792_000_000_000_000_000           # the record's clock: epoch ns
PHASES = [("engine.admit", 0.0, 1.0), ("engine.plan", 1.0, 1.5),
          ("engine.build", 1.5, 3.0), ("engine.dispatch", 3.0, 5.0),
          ("engine.wait", 5.01, 900.0), ("engine.advance", 900.0, 902.0),
          ("engine.readmit", 902.0, 902.1)]           # ms into the tick


def reader(name):
    path = os.path.join(HERE, "..", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Record:
    """Stands for the engine's `TickPhases`: what the readers call."""

    def __init__(self, ticks):
        self._ticks = ticks

    def ticks(self):
        return list(self._ticks)


def run_of(trace, rec_ticks, traced, engine=True):
    handle = NS(engine=NS(phases=Record(rec_ticks))) if engine else NS()
    return NS(trace=trace,
              record=NS(flights=[NS(handle=handle)],
                        ticks=[NS(traced=t) for t in traced]))


def hand_made(shift_ms=0.0):
    """Two fill ticks and three window ticks of 1 s; the last two are
    traced. The device runs from 1 ms into dispatch to the end of wait, so
    6.1 ms of each tick's 7.1 ms of host work leave it idle."""
    rec, host, ops = [], [], []
    origin = T0 + 3 * 1000 * MS - 10 * MS      # the trace's session start
    for k in range(5):
        base = T0 + k * 1000 * MS
        if k == 4:
            base += int(shift_ms * MS)
        rec.append((k, tuple((n, base + int(a * MS), base + int(b * MS))
                             for n, a, b in PHASES)))
        if k < 3:
            continue
        t = (T0 + k * 1000 * MS - origin) / 1e9   # tick start, trace clock
        host += [("step_begin", t - 5e-6, t + 5.005e-3),
                 ("step_end", t + 5.008e-3, t + 902.105e-3),
                 ("collect", t + 902.11e-3, t + 902.2e-3)]
        ops += [("%closed_call.1 tpu_custom_call", t + 4e-3, t + 0.5),
                ("%ams_matmul_padded.2 tpu_custom_call", t + 0.5, t + 0.9)]
    trace = Trace(ops={0: ops}, modules={0: []}, host=host)
    return run_of(trace, rec, [False, True, True])


def test_readers_on_a_hand_made_trace():
    run = hand_made()
    offs, _ = progspans.offsets(run)
    assert max(offs) - min(offs) < 1e-9
    ticks = progspans.placed(run)
    assert len(ticks) == 2
    spans = [e for e in run.trace.host if e[0] == "step_begin"]
    for tick, (_, a, b) in zip(ticks, spans):
        assert [n for n, _, _ in tick] == [n for n, _, _ in PHASES]
        # the median offset lays each tick's start on its span's start
        assert tick[0][1] == pytest.approx(a, abs=1e-9) and a < b
    assert reader("host_ms_per_tick")(run) == pytest.approx(7.1, abs=1e-6)
    assert reader("host_stall_ms_per_tick")(run) == pytest.approx(6.1,
                                                                  abs=1e-6)


def test_offsets_that_spread_give_no_value():
    run = hand_made(shift_ms=0.5)
    offs, _ = progspans.offsets(run)
    assert max(offs) - min(offs) == pytest.approx(5e-4, abs=1e-9)
    assert progspans.placed(run) is None
    assert reader("host_ms_per_tick")(run) is None
    assert reader("host_stall_ms_per_tick")(run) is None
    # within the limit the readers still read
    assert reader("host_ms_per_tick")(hand_made(shift_ms=0.05)) is not None


def test_no_record_or_no_pairing_gives_no_value():
    run = hand_made()
    bare = run_of(run.trace, [], [False, True, True], engine=False)
    assert progspans.engine_ticks(bare) is None
    short = run_of(run.trace, run.record.flights[0].handle.engine.phases
                   .ticks(), [False, False, True])     # 1 tick, 2 spans
    untraced = run_of(None, [], [False])
    for r in (bare, short, untraced):
        assert reader("host_ms_per_tick")(r) is None
        assert reader("host_stall_ms_per_tick")(r) is None


def test_idle_inside():
    busy = [(1.0, 2.0), (3.0, 4.0)]
    assert progspans.idle_inside(busy, 0.0, 5.0) == pytest.approx(3.0)
    assert progspans.idle_inside(busy, 1.5, 3.5) == pytest.approx(1.0)
    assert progspans.idle_inside(busy, 1.2, 1.8) == pytest.approx(0.0)
    assert progspans.idle_inside([], 0.0, 1.0) == pytest.approx(1.0)


def recorded():
    """The traced slice of one qwen2-7b-ams5.decode run on a TPU v5 lite
    (seed 3021500101): `devtrace.load`'s reduction, the driver's window
    ticks (which were traced) and the engine's phase record of the same
    ticks, as a scratch hook around ``run.main`` saved them."""
    with gzip.open(os.path.join(DATA, "decode_phases_v5e.json.gz"), "rt") as f:
        d = json.load(f)
    return run_of(Trace.from_json(d["trace"]), d["phases"], d["traced"])


def test_readers_on_ticks_recorded_on_a_v5e():
    run = recorded()
    offs, _ = progspans.offsets(run)
    assert len(offs) == 2 and max(offs) - min(offs) <= progspans.MAX_SPREAD_S
    host = reader("host_ms_per_tick")(run)
    stall = reader("host_stall_ms_per_tick")(run)
    assert host == pytest.approx(5.6085, abs=1e-3)     # the run's own line
    assert stall == pytest.approx(4.3792, abs=1e-3)
    assert 0 < stall <= host
    ticks = progspans.placed(run)
    for tick in ticks:                    # the wait is over 99 % of a tick
        wait = sum(b - a for n, a, b in tick if n == progspans.WAIT)
        assert wait > 0.99 * (tick[-1][2] - tick[0][1])
    # all the engine's phases together hold over 80 % of the window's idle
    w = devtrace.window(run.trace)
    idle = (w[1] - w[0]) - devtrace.busy_seconds(run.trace)
    assert progspans.stall_seconds(run.trace, ticks, skip=()) > 0.8 * idle
