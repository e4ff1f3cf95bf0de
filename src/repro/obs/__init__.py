"""Serving observability: metrics registry, trace spans, roofline cost.

Three layers, wired through the serving stack (see docs/observability.md):

  * `obs.metrics`  — labelled counters/gauges/histograms with Prometheus
    text exposition and JSONL snapshots; `ServeEngine.stats()` is computed
    from this registry;
  * `obs.trace`    — engine tick phases on the JAX profiler's clock
    (always recorded, bounded; also profiler annotations) and per-request
    lifecycle spans as Chrome trace-event JSON (load in Perfetto);
  * `obs.cost`     — analytic HBM-byte / FLOP floors per engine-step
    signature, accumulated per tick and per request, plus the compiled
    step's parsed HLO cost as the achieved side.

`ObsConfig(enabled=False)` swaps in no-op instruments end to end —
telemetry can never perturb the measured system (asserted by the bench
``--obs-check`` mode).
"""

from repro.obs.config import ObsConfig
from repro.obs.cost import (
    StepCostModel,
    attribution,
    build_cost_model,
    hlo_step_cost,
    kv_vector_bytes_floor,
    kv_vector_bytes_ideal,
)
from repro.obs.metrics import (
    NULL_REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prom,
    ticker_line,
)
from repro.obs.trace import TickPhases, TraceRecorder, now_ns, validate_events

__all__ = [
    "ObsConfig", "MetricsRegistry", "NULL_REGISTRY",
    "Counter", "Gauge", "Histogram", "parse_prom", "ticker_line",
    "TickPhases", "TraceRecorder", "now_ns", "validate_events",
    "StepCostModel", "build_cost_model", "attribution", "hlo_step_cost",
    "kv_vector_bytes_floor", "kv_vector_bytes_ideal",
]
