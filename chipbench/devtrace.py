"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps, each gap named by what the host was doing in it.

`load` reads the ``.xplane.pb`` the JAX profiler writes, with JAX's own
`ProfileData`: the device planes (``/device:TPU:<n>``) give one event per
device operation on their ``XLA Ops`` line and one per program execution on
their ``XLA Modules`` line; the host plane gives the benchmark's own
`TraceAnnotation` spans (`driver.Driver`). The rest of this module works on
plain (name, start, end) tuples in seconds, so a test can feed it a small
recorded trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start s, end s)

HOST_SPANS = ("submit", "step_begin", "step_end", "collect")


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]           # device index -> its operations
    modules: Dict[int, List[Event]]       # device index -> program runs
    host: List[Event]                     # the benchmark's host spans

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def ev(xs):
            return [(str(n), float(a), float(b)) for n, a, b in xs]
        return cls({int(k): ev(v) for k, v in d["ops"].items()},
                   {int(k): ev(v) for k, v in d["modules"].items()},
                   ev(d["host"]))


def op_name(text: str) -> str:
    """An operation's name as the reduction keeps it: the HLO instruction's
    name (the trace gives its whole text), marked ``tpu_custom_call`` when
    it is a Pallas kernel."""
    name = text.split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in text:
        name += " tpu_custom_call"
    return name


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that hold no other event: a loop or call op on the ops
    line spans the operations it runs."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][1] >= e[2]]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            for line in plane.lines:
                if dev is not None and line.name in ("XLA Ops", "XLA Modules"):
                    out = (ops if line.name == "XLA Ops" else modules
                           ).setdefault(int(dev.group(1)), [])
                    out.extend((op_name(e.name), e.start_ns * 1e-9,
                                e.end_ns * 1e-9) for e in line.events)
                elif plane.name == "/host:CPU":
                    host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                for e in line.events if e.name in HOST_SPANS)
    return Trace(ops, modules, sorted(host, key=lambda e: e[1]))


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end] intervals covered by at least one event."""
    out: List[List[float]] = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window(tr: Trace) -> Optional[Tuple[float, float]]:
    """The traced window: from the first traced tick's ``step_begin`` to the
    end of the last host span, or the device operations' extent when the
    trace holds no host spans."""
    ticks = [e for e in tr.host if e[0] == "step_begin"]
    if ticks:
        return ticks[0][1], max(e[2] for e in tr.host)
    evs = [e for dev in tr.ops.values() for e in dev]
    if not evs:
        return None
    return min(e[1] for e in evs), max(e[2] for e in evs)


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def busy_seconds(tr: Trace) -> Optional[float]:
    """Union of device-operation time inside the window, averaged over the
    chips traced."""
    w = window(tr)
    if w is None or not tr.ops:
        return None
    per = [sum(b - a for a, b in union(clip(evs, *w))) for evs in tr.ops.values()]
    return sum(per) / len(per)


def kernel_seconds(tr: Trace, patterns: Sequence[str]) -> Optional[float]:
    """Summed device durations of the operations whose name matches any of
    ``patterns`` (regular expressions), averaged over the chips traced;
    None when no operation matches."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    w = window(tr)
    if w is None:
        return None
    per = []
    for evs in tr.ops.values():
        hit = [b - a for n, a, b in clip(evs, *w) if rx.search(n)]
        if hit:
            per.append(sum(hit))
    return sum(per) / len(per) if per else None


def module_runs(tr: Trace, pattern: str) -> int:
    """How many times a program whose name matches ``pattern`` ran inside
    the window, on the busiest chip."""
    w = window(tr)
    rx = re.compile(pattern)
    if w is None or not tr.modules:
        return 0
    return max(sum(1 for n, a, b in evs if rx.search(n) and w[0] <= a < w[1])
               for evs in tr.modules.values())


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The n device operations that took most time on chip 0, seconds
    summed over their runs and over instructions of the same kind (the
    name without its instance number); loops and calls that only contain
    other operations are left out."""
    w = window(tr)
    if w is None or not tr.ops:
        return []
    tot: Dict[str, float] = {}
    for name, a, b in clip(leaves(tr.ops[min(tr.ops)]), *w):
        name = re.sub(r"\.\d+", "", name)
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The n longest idle stretches of chip 0 inside the window, each named
    by the host span that overlaps it most (``host`` when none does)."""
    w = window(tr)
    if w is None or not tr.ops:
        return []
    busy = union(clip(tr.ops[min(tr.ops)], *w))
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        best, name = 0.0, "host"
        for sn, sa, sb in tr.host:
            ov = min(b, sb) - max(a, sa)
            if ov > best:
                best, name = ov, sn
        out.append([name, b - a])
    return out
