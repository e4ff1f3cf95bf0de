"""The closed loop that drives the engine, in set-up and in the window.

It drives `ServeEngine` through its public calls, as the front end's driver
thread does (`submit`, then `step_begin` / `step_end` in a loop), in this one
process, and stamps every event with the host clock: when each tick began
and ended, and which tokens each tick delivered. Every client submits its
next request the tick its last one finishes.

`fill` is set-up: the clients' first requests are submitted together and
stepped until every one of them has its first token, so that the window
opens on a full decode batch and not on a cold wave of prefills. `run` is
the window. With ``trace_dir`` set, a slice of the window is profiled, with
the benchmark's own host spans around every engine call.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Flight:
    """One request as its client saw it: when each token was delivered, in
    seconds from the window's start (negative for tokens set-up delivered)."""

    req: object               # traffic.Request
    handle: object            # the engine's RequestHandle
    token_times: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.handle.done

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


@dataclasses.dataclass
class Tick:
    begin: float
    end: float
    active: int               # slots serving a request after admission
    fed: np.ndarray           # per-slot start positions fed this tick
    nvalid: np.ndarray        # per-slot tokens fed this tick (0 = idle)
    traced: bool = False


@dataclasses.dataclass
class Record:
    seconds: float
    flights: List[Flight]
    ticks: List[Tick]         # the window's ticks, the last one past its end


class Driver:
    def __init__(self, eng, mix, clients: int, *, trace_dir=None,
                 trace_at=0.4, trace_seconds=3.0):
        self.eng = eng
        self.mix = mix
        self.clients = clients
        self.trace_dir = trace_dir
        self.trace_at = trace_at
        self.trace_seconds = trace_seconds
        self.flights: List[Flight] = []
        self.live: Dict[int, Flight] = {}
        self.ticks: List[Tick] = []
        self.t0 = time.perf_counter()
        self.fill_ticks = 0
        self.fill_s = 0.0
        self.close = float("inf")   # no client submits after the window
        self._tracing = False

    def _span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _now(self) -> float:
        return time.perf_counter() - self.t0

    def _submit(self) -> Flight:
        r = self.mix.next_closed()
        with self._span("submit"):
            h = self.eng.submit(r.prompt, max_tokens=r.max_tokens)
        f = Flight(r, h)
        self.flights.append(f)
        self.live[id(f)] = f
        return f

    def _tick(self, traced: bool) -> None:
        """One engine tick; each client whose request finished in it
        submits its next one."""
        eng = self.eng
        begin = self._now()
        with self._span("step_begin"):
            p = eng.step_begin()
        fed = eng.fed.copy()
        nvalid = (np.zeros_like(fed) if p.nvalid is None
                  else np.asarray(p.nvalid).copy())
        active = eng.active_count
        with self._span("step_end"):
            eng.step_end(p)
        end = self._now()
        done = 0
        with self._span("collect"):
            for key, f in list(self.live.items()):
                n = len(f.handle.request.tokens)
                f.token_times.extend([end] * (n - len(f.token_times)))
                if f.done:
                    del self.live[key]
                    done += 1
        self.ticks.append(Tick(begin, end, active, fed, nvalid, traced))
        if end < self.close:
            for _ in range(done):
                self._submit()

    def _trace_control(self, seconds: float) -> bool:
        """Start the profiler at a tick boundary ``trace_at`` into the
        window and stop it ``trace_seconds`` later; returns whether the
        next tick is traced."""
        if self.trace_dir is None:
            return False
        import jax
        now = self._now()
        start = self.trace_at * seconds
        if not self._tracing and start <= now < start + self.trace_seconds:
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
        elif self._tracing and now >= start + self.trace_seconds:
            jax.profiler.stop_trace()
            self._tracing = False
        return self._tracing

    def fill(self) -> None:
        """Set-up: submit every client's first request and step until each
        of them has its first token."""
        t = time.perf_counter()
        wave = [self._submit() for _ in range(self.clients)]
        while any(f.first is None for f in wave):
            self._tick(False)
        self.fill_ticks = len(self.ticks)
        self.fill_s = time.perf_counter() - t
        self.ticks = []

    def run(self, seconds: float) -> Record:
        """The window: step the closed loop until ``seconds`` have passed.
        The tick under way at the close runs to its end, and no later one
        starts."""
        # the window's clock: every earlier time becomes negative
        shift = time.perf_counter() - self.t0
        self.t0 += shift
        self.close = seconds
        for f in self.flights:
            f.token_times = [x - shift for x in f.token_times]
        while self._now() < seconds and self.eng.has_work:
            self._tick(self._trace_control(seconds))
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False
        return Record(seconds, self.flights, self.ticks)
