"""Observability configuration for the serving engine.

One `ObsConfig` selects how much telemetry the engine records, in the same
frozen-dataclass style as `cache.CacheConfig`:

  * ``enabled=True``  (default) — the engine creates a live
    `MetricsRegistry` and emits counters/gauges/histograms on every tick;
    `ServeEngine.stats()` is computed from the registry. It also records
    every tick's phases (`obs.trace.TickPhases`: a bounded in-memory
    record on the JAX profiler's clock, a counter per phase, and a
    ``jax.profiler.TraceAnnotation`` per phase). Recording is a handful of
    float adds and clock reads per tick, and nothing waits on the device,
    so the measured system is not perturbed (asserted by the bench
    ``--obs-check`` run and tests/test_obs.py).
  * ``enabled=False`` — every instrument is the shared no-op
    `NULL_REGISTRY` child: call sites stay branch-free and accumulated
    telemetry reads as zero. Pure-state stats (kv bytes/token, queue
    depth) remain real.
  * ``trace=True`` — additionally keep per-request lifecycle spans and the
    tick phases as a Chrome trace (`obs.trace.TraceRecorder`), on the same
    clock. It adds no wait on the device either.
  * ``cost=True`` (default) — attach the analytic roofline cost model
    (`obs.cost.StepCostModel`) and accumulate per-tick / per-request
    floor-vs-achieved HBM byte accounting.

A device-level capture is the operator's own ``jax.profiler`` session: the
phase annotations show up in whatever window it covers.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """How much telemetry the serving engine records."""

    enabled: bool = True        # master switch: False -> no-op instruments
    trace: bool = False         # keep lifecycle + tick spans (Chrome)
    cost: bool = True           # roofline floor/achieved byte accounting

    @property
    def trace_on(self) -> bool:
        return self.enabled and self.trace

    @property
    def cost_on(self) -> bool:
        return self.enabled and self.cost
