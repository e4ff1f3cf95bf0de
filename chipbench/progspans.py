"""The program's own tick phases, placed on a profiler trace's timeline.

The serving engine stamps every tick's phases (``engine.admit`` ...
``engine.readmit``, `repro.obs.trace.TickPhases`) on the JAX profiler's
host clock and keeps the last few thousand ticks in memory. `devtrace.load`
keeps only the benchmark's own host spans, so the readers take the phases
from the engine, through the handles the run keeps
(``run.record.flights[i].handle.engine.phases``); a program without them
gives no value.

The trace's times count from its session's start; the record's are
absolute. The traced ticks are the driver's ticks flagged ``traced``: the
engine runs one tick per driver tick and none after the window, so they are
the record's matching last ticks. Each began inside its ``step_begin`` span,
a few microseconds after the span opened, so one offset per trace, the
median over the traced ticks of (span start - tick start), places the
phases on the trace. Where the ticks and the spans do not pair one for one,
or their offsets spread by more than `MAX_SPREAD_S`, nothing is placed.
"""

from __future__ import annotations

import bisect
import statistics
from typing import List, Optional, Sequence, Tuple

import devtrace

Event = Tuple[str, float, float]          # (name, start s, end s)

MAX_SPREAD_S = 1e-4
WAIT = "engine.wait"                      # the phase that waits on the device


def engine_ticks(run):
    """The engine's recorded ticks, oldest first, as (tick, ((name, start ns,
    end ns), ...)); None when the program keeps no such record."""
    for f in run.record.flights:
        phases = getattr(getattr(f.handle, "engine", None), "phases", None)
        if phases is not None:
            return phases.ticks()
    return None


def offsets(run) -> Optional[Tuple[List[float], List[Sequence]]]:
    """Per traced tick, the offset (s) from the record's clock, counted from
    the first traced tick's start, to the trace's; and the ticks' phases.
    None when the record and the trace's ``step_begin`` spans do not pair
    one for one."""
    if run.trace is None:
        return None
    rec = engine_ticks(run)
    ticks = run.record.ticks
    spans = [e for e in run.trace.host if e[0] == "step_begin"]
    traced = [i for i, t in enumerate(ticks) if t.traced]
    if not rec or not spans or len(spans) != len(traced) \
            or len(rec) < len(ticks):
        return None
    base = len(rec) - len(ticks)
    phases = [rec[base + i][1] for i in traced]
    if not all(phases):
        return None
    ref = phases[0][0][1]
    return ([a - (ph[0][1] - ref) * 1e-9 for (_, a, _), ph in zip(spans, phases)],
            [[(n, a - ref, b - ref) for n, a, b in ph] for ph in phases])


def placed(run) -> Optional[List[List[Event]]]:
    """The traced ticks' phases on the trace's timeline, in seconds; None
    when there are none, or when their offsets spread by more than
    `MAX_SPREAD_S`."""
    got = offsets(run)
    if got is None:
        return None
    offs, phases = got
    if max(offs) - min(offs) > MAX_SPREAD_S:
        return None
    off = statistics.median(offs)
    return [[(n, a * 1e-9 + off, b * 1e-9 + off) for n, a, b in ph]
            for ph in phases]


def host_seconds(tick: Sequence[Event]) -> float:
    """A tick's host work: its phases other than the wait on the device."""
    return sum(b - a for n, a, b in tick if n != WAIT)


def idle_inside(busy: List[Tuple[float, float]], a: float, b: float) -> float:
    """Time in [a, b] that no interval of ``busy`` (sorted, disjoint)
    covers."""
    covered = 0.0
    i = max(bisect.bisect_right(busy, (a, a)) - 1, 0)
    while i < len(busy) and busy[i][0] < b:
        covered += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
        i += 1
    return (b - a) - covered


def stall_seconds(trace, ticks: List[List[Event]],
                  skip: Sequence[str] = (WAIT,)) -> Optional[float]:
    """Device-idle time inside the ticks' phases (those not in ``skip``),
    within the traced window, averaged over the chips traced."""
    w = devtrace.window(trace)
    if w is None or not trace.ops:
        return None
    per = []
    for evs in trace.ops.values():
        busy = devtrace.union(devtrace.clip(evs, *w))
        per.append(sum(idle_inside(busy, max(a, w[0]), min(b, w[1]))
                       for tick in ticks for n, a, b in tick
                       if n not in skip and min(b, w[1]) > max(a, w[0])))
    return sum(per) / len(per)
