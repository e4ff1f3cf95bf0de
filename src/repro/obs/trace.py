"""Engine tick phases and per-request lifecycle spans, on the profiler's clock.

**Clock.** Every stamp is `now_ns` (``time.time_ns``, CLOCK_REALTIME): the
clock the JAX profiler stamps its host events with (TSL's
``GetCurrentTimeNanos``, the wall clock). A capture's ``.xplane.pb`` stores event times relative to the session start,
and its ``Task Environment`` plane holds that start as the
``profile_start_time`` stat in the same nanoseconds, so a stamp minus
``profile_start_time`` is the event's time on the device trace's timeline
(held to the profiler's own events by tests/test_obs.py).

**Tick phases** (`TickPhases`, always on while ``ObsConfig.enabled``). The
engine splits every tick at the boundaries its code already has::

    step_begin   engine.admit      admission and preemption
                 engine.plan       chunk sizing under the token budget, drafts
                 engine.build      the ragged host arrays and their transfers
                 engine.dispatch   the jitted step call, until it returns
    step_end     engine.wait       until the step's outputs are on the host
                 engine.advance    slot state, cost accounting, pages, finishing
                 engine.readmit    the same-tick re-admission

(an idle tick has only ``engine.admit``). Each boundary is stamped once, by
`TickPhases.mark`, and that stamp feeds a bounded record of the last
``capacity`` ticks, the registry counter
``serve_tick_phase_seconds_total{phase}`` and, when tracing, the Chrome
export. Each phase is also entered as a ``jax.profiler.TraceAnnotation`` of
the same name, so any profiler capture shows the phases on the host line
beside the device ops. Nothing here waits on the device: ``engine.wait``
ends where `step_end`'s first read of the outputs returns. No name here is
``submit``, ``step_begin``, ``step_end`` or ``collect``: a benchmark that
drives the engine keeps its own host spans under those names.

**Lifecycle spans** (`TraceRecorder`, behind ``ObsConfig.trace``). One span
tree per request on its own trace "thread" (tid = rid + 1)::

    request
      queued                submit -> admitted
      prefill               admitted -> first generated token
      decode                first token -> finish
      finished  (instant)

plus the engine thread (tid 0): one ``tick`` span per tick with its phases
nested inside, and ``active`` / ``queue`` counter tracks.

The export (`save` / `chrome`) is the Chrome trace-event format: load the
JSON at https://ui.perfetto.dev or chrome://tracing, with ``ts`` in
microseconds on `now_ns`. ``B``/``E`` events require strict LIFO nesting per
thread — `end` enforces it eagerly (a mis-nested span raises at the
recording site, not at viewing time), and `validate_events` re-checks a
finished event stream structurally, which is what tests/test_obs.py runs
against random traffic.

A disabled recorder (``TraceRecorder(enabled=False)``, ``TickPhases(
enabled=False)``) early-returns from every method: the zero-perturbation
guarantee of `ObsConfig` again reduces to a no-op call per event.
"""

from __future__ import annotations

import collections
import json
from time import time_ns
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

from repro.obs.metrics import NULL_REGISTRY

now_ns = time_ns  # the JAX profiler's host clock (module docstring)

PID = 1          # single-process engine: one trace process


class TraceRecorder:
    """Span recorder with eager nesting validation (module docstring)."""

    def __init__(self, enabled: bool = True, clock=now_ns):
        self.enabled = enabled
        self._clock = clock
        self._events: List[dict] = []
        self._stacks: Dict[int, List[str]] = {}
        self._named: Dict[int, str] = {}

    def _ts(self, ns: Optional[int] = None) -> float:
        return (self._clock() if ns is None else ns) / 1e3    # ns -> us

    # ------------------------------------------------------------ recording
    def thread(self, tid: int, name: str) -> None:
        """Name a trace thread (one per request, plus tid 0 = engine)."""
        if not self.enabled or self._named.get(tid) == name:
            return
        self._named[tid] = name
        self._events.append({"ph": "M", "pid": PID, "tid": tid, "ts": 0,
                             "name": "thread_name", "args": {"name": name}})

    def begin(self, tid: int, name: str, args: Optional[dict] = None,
              ns: Optional[int] = None) -> None:
        """Open a span, stamped now or at ``ns`` (a stamp already taken)."""
        if not self.enabled:
            return
        ev = {"ph": "B", "pid": PID, "tid": tid, "name": name,
              "ts": self._ts(ns)}
        if args:
            ev["args"] = args
        self._events.append(ev)
        self._stacks.setdefault(tid, []).append(name)

    def end(self, tid: int, name: str, args: Optional[dict] = None,
            ns: Optional[int] = None) -> None:
        if not self.enabled:
            return
        stack = self._stacks.get(tid, [])
        if not stack or stack[-1] != name:
            raise RuntimeError(
                f"span nesting violated on tid {tid}: end({name!r}) but "
                f"open spans are {stack}")
        stack.pop()
        ev = {"ph": "E", "pid": PID, "tid": tid, "name": name,
              "ts": self._ts(ns)}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def nested(self, tid: int, name: str,
               children: Sequence[Tuple[str, int, int]],
               args: Optional[dict] = None) -> None:
        """One span from the first child's start to the last child's end,
        with the (name, start ns, end ns) children inside it: how a tick
        and its phases, stamped as they ran, reach the export."""
        if not self.enabled or not children:
            return
        self.begin(tid, name, args, ns=children[0][1])
        for child, a, b in children:
            self.begin(tid, child, ns=a)
            self.end(tid, child, ns=b)
        self.end(tid, name, ns=children[-1][2])

    def instant(self, tid: int, name: str, args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"ph": "i", "s": "t", "pid": PID, "tid": tid, "name": name,
              "ts": self._ts()}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def counter(self, name: str, values: Dict[str, float],
                tid: int = 0) -> None:
        """Counter track (rendered as a stacked area chart in Perfetto)."""
        if not self.enabled:
            return
        self._events.append({"ph": "C", "pid": PID, "tid": tid, "name": name,
                             "ts": self._ts(), "args": dict(values)})

    # -------------------------------------------------------------- queries
    def open_spans(self) -> Dict[int, List[str]]:
        """Still-open spans per tid — empty when every span closed (the
        lifecycle invariant the tests assert after a drained workload)."""
        return {tid: list(s) for tid, s in self._stacks.items() if s}

    def events(self) -> List[dict]:
        return list(self._events)

    # --------------------------------------------------------------- export
    def chrome(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome(), f)


PHASE_PREFIX = "engine."
BEGIN_PHASES = ("admit", "plan", "build", "dispatch")    # in step_begin
END_PHASES = ("wait", "advance", "readmit")              # in step_end
PHASES = BEGIN_PHASES + END_PHASES

Phase = Tuple[str, int, int]          # (span name, start ns, end ns)


class TickPhases:
    """The engine's tick phases: one stamp per boundary, a bounded record
    of the last ``capacity`` ticks, a registry counter per phase and a
    profiler annotation per phase (module docstring)."""

    def __init__(self, enabled: bool = True, capacity: int = 4096,
                 metrics=NULL_REGISTRY, clock=now_ns):
        self.enabled = enabled
        self.capacity = capacity
        self._clock = clock
        self._ticks: Deque[Tuple[int, Tuple[Phase, ...]]] = \
            collections.deque(maxlen=capacity)
        self._cur: List[Phase] = []
        self._open: Optional[str] = None
        self._since = 0
        self._ann: Optional[TraceAnnotation] = None
        fam = metrics.counter("serve_tick_phase_seconds_total",
                              "host seconds spent in each engine tick phase",
                              ("phase",))
        self._seconds = {PHASE_PREFIX + p: fam.labels(phase=p) for p in PHASES}

    def begin_tick(self) -> int:
        """Open a tick at its first phase, ``engine.admit``, dropping what a
        tick that raised half-way left open."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._cur, self._open, self._ann = [], None, None
        return self.mark("admit")

    def mark(self, phase: Optional[str] = None) -> int:
        """THE recording site: close the open phase and open ``phase``
        (none when None) at one stamp, which it returns."""
        now = self._clock()
        if not self.enabled:
            return now
        if self._open is not None:
            self._ann.__exit__(None, None, None)
            self._cur.append((self._open, self._since, now))
            self._seconds[self._open].inc((now - self._since) * 1e-9)
            self._open = self._ann = None
        if phase is not None:
            self._open, self._since = PHASE_PREFIX + phase, now
            self._ann = TraceAnnotation(self._open)
            self._ann.__enter__()
        return now

    def close_tick(self, tick: int) -> Tuple[Phase, ...]:
        """Close the open phase and file the tick's phases under ``tick``."""
        self.mark(None)
        phases, self._cur = tuple(self._cur), []
        if self.enabled:
            self._ticks.append((tick, phases))
        return phases

    def ticks(self) -> List[Tuple[int, Tuple[Phase, ...]]]:
        """The recorded ticks, oldest first: (engine tick, phases)."""
        return list(self._ticks)


def validate_events(events: List[dict]) -> Dict[int, List[Tuple[str, float, float, int]]]:
    """Structural check of a finished trace-event stream: per-tid LIFO
    B/E pairing, no dangling opens, and non-decreasing timestamps per tid.
    Returns the reconstructed spans {tid: [(name, ts_begin, ts_end,
    depth)]}; raises AssertionError on any violation."""
    stacks: Dict[int, List[Tuple[str, float]]] = {}
    last_ts: Dict[int, float] = {}
    spans: Dict[int, List[Tuple[str, float, float, int]]] = {}
    for ev in events:
        tid = ev["tid"]
        ph = ev["ph"]
        if ph == "M":
            continue
        ts = ev["ts"]
        assert ts >= last_ts.get(tid, 0.0), (
            f"tid {tid}: timestamp went backwards ({ts} < {last_ts[tid]})")
        last_ts[tid] = ts
        if ph == "B":
            stacks.setdefault(tid, []).append((ev["name"], ts))
        elif ph == "E":
            stack = stacks.get(tid, [])
            assert stack, f"tid {tid}: E {ev['name']!r} with no open span"
            name, ts_b = stack.pop()
            assert name == ev["name"], (
                f"tid {tid}: E {ev['name']!r} does not match open span "
                f"{name!r} (mis-nesting)")
            spans.setdefault(tid, []).append((name, ts_b, ts, len(stack)))
        elif ph not in ("i", "C"):
            raise AssertionError(f"unexpected phase {ph!r}")
    dangling = {tid: [n for n, _ in s] for tid, s in stacks.items() if s}
    assert not dangling, f"spans never closed: {dangling}"
    return spans
