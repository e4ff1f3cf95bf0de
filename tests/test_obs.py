"""Observability subsystem (repro.obs): registry, spans, roofline floors.

The load-bearing contracts:

  (a) ZERO PERTURBATION: telemetry on vs `ObsConfig(enabled=False)` is
      bit-identical in engine behaviour — same ticks, same token streams,
      same lifecycle ticks (the committed bench baseline depends on this);
  (b) `stats()` is a VIEW over the registry: every legacy key reproduces
      the pre-telemetry hand-counter math exactly (the raw-observation
      histograms keep insertion order, so percentiles can't drift);
  (c) span lifecycle invariants hold under real traffic — queueing,
      chunked prefill, same-tick re-admission, speculative rollback:
      strict LIFO nesting per track, every span closed at drain; every
      tick's phases tile it in order, sit on the JAX profiler's clock, and
      never wait on the device;
  (d) the analytic KV floors in `obs.cost` are derived INDEPENDENTLY of
      `repro.cache` and must agree with the pool layout exactly — the
      measured engine bytes/token sits within 10% of the floor (it is
      exactly 1.0x), and layout drift in either module trips the test;
  (e) the Prometheus exposition round-trips through `parse_prom`.
"""

import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.cache import CacheConfig
from repro.cache.pool import pool_bytes_per_token
from repro.core.formats import get_scheme
from repro.launch.engine import ServeEngine
from repro.launch.sampling import SamplingParams
from repro.obs import (
    MetricsRegistry,
    ObsConfig,
    TickPhases,
    TraceRecorder,
    attribution,
    build_cost_model,
    kv_vector_bytes_floor,
    kv_vector_bytes_ideal,
    parse_prom,
    ticker_line,
    validate_events,
)
from repro.obs.metrics import NULL_REGISTRY
from repro.obs.trace import BEGIN_PHASES, END_PHASES, PHASE_PREFIX

ARCH = "qwen2-7b"
SCHEME = "fp5.33-e2m3"
PAGE = 8
PREFIX = 16


# ===================================================== registry (no engine)
class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        m = MetricsRegistry()
        c = m.counter("c_total", "help")
        c.inc()
        c.inc(2.5)
        assert m.value("c_total") == 3.5
        g = m.gauge("g", "help")
        g.set(7)
        assert m.value("g") == 7.0
        h = m.histogram("h", "help", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 3 and h.sum == 55.5
        assert h.raw_values() == [0.5, 5.0, 50.0]   # insertion order

    def test_labels_get_or_create(self):
        m = MetricsRegistry()
        c = m.counter("req_total", "help", labelnames=("kind",))
        c.labels(kind="a").inc(2)
        c.labels(kind="b").inc(3)
        assert m.value("req_total", kind="a") == 2.0
        assert c.total == 5.0
        # same name, conflicting shape -> loud failure, not silent aliasing
        with pytest.raises(ValueError):
            m.counter("req_total", "help", labelnames=("other",))
        with pytest.raises(ValueError):
            m.gauge("req_total", "help")

    def test_callback_gauge_survives_reset(self):
        m = MetricsRegistry()
        state = {"v": 1.0}
        g = m.gauge("depth", "help", fn=lambda: state["v"])
        state["v"] = 42.0
        assert g.value == 42.0                # sampled at read time
        assert m.value("depth") == 42.0
        m.reset()
        assert g.value == 42.0                # reset keeps the callback

    def test_disabled_registry_is_inert(self):
        m = MetricsRegistry(enabled=False)
        c = m.counter("c_total", "help")
        c.inc(5)
        h = m.histogram("h", "help")
        h.observe(1.0)
        assert m.value("c_total") == 0.0
        assert h.raw_values() == []
        assert c is m.counter("other_total", "help")   # shared no-op
        assert NULL_REGISTRY.counter("x_total", "h").value == 0.0

    def test_exposition_round_trip(self):
        m = MetricsRegistry()
        m.counter("req_total", "reqs", labelnames=("mode",)).labels(
            mode='pa"ged\\x').inc(3)
        m.gauge("depth", "queue").set(2.5)
        h = m.histogram("lat_s", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        parsed = parse_prom(m.exposition())
        assert parsed[("req_total", (("mode", 'pa"ged\\x'),))] == 3.0
        assert parsed[("depth", ())] == 2.5
        # cumulative buckets + exact sum/count
        assert parsed[("lat_s_bucket", (("le", "0.1"),))] == 1.0
        assert parsed[("lat_s_bucket", (("le", "1"),))] == 2.0
        assert parsed[("lat_s_bucket", (("le", "+Inf"),))] == 3.0
        assert parsed[("lat_s_count", ())] == 3.0
        assert parsed[("lat_s_sum", ())] == pytest.approx(5.55)

    def test_snapshot_jsonl(self, tmp_path):
        m = MetricsRegistry()
        m.counter("c_total", "help").inc(2)
        p = tmp_path / "m.jsonl"
        m.write_jsonl(str(p), extra={"run": "t1"})
        m.write_jsonl(str(p))
        lines = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert len(lines) == 2 and lines[0]["run"] == "t1"
        fam = lines[1]["metrics"]["c_total"]
        assert fam["type"] == "counter"
        assert fam["values"][0]["value"] == 2.0


# ======================================================== spans (no engine)
class TestTrace:
    def _rec(self):
        t = {"now": 1_000_000}
        rec = TraceRecorder(clock=lambda: t["now"])
        return rec, t

    def test_nesting_and_export(self, tmp_path):
        rec, t = self._rec()
        rec.thread(0, "engine")
        rec.begin(0, "tick")
        t["now"] += 3000
        rec.begin(0, "engine.wait")
        t["now"] += 2000
        rec.end(0, "engine.wait")
        rec.instant(0, "finished")
        rec.counter("engine", {"active": 2})
        rec.end(0, "tick", args={"generated": 1})
        # stamps taken elsewhere, exported as one span with its children
        rec.nested(1, "tick", [("engine.admit", 9_000_000, 9_004_000),
                               ("engine.plan", 9_004_000, 9_006_000)])
        assert rec.open_spans() == {}
        spans = validate_events(rec.events())
        names = [(n, d) for n, _, _, d in spans[0]]
        assert ("tick", 0) in names and ("engine.wait", 1) in names
        assert spans[1] == [("engine.admit", 9000.0, 9004.0, 1),
                            ("engine.plan", 9004.0, 9006.0, 1),
                            ("tick", 9000.0, 9006.0, 0)]
        p = tmp_path / "trace.json"
        rec.save(str(p))
        dumped = json.loads(p.read_text())
        phases = {e["ph"] for e in dumped["traceEvents"]}
        assert {"B", "E", "M", "i", "C"} <= phases

    def test_mismatched_end_raises_eagerly(self):
        rec, _ = self._rec()
        rec.begin(0, "tick")
        with pytest.raises(RuntimeError, match="nesting"):
            rec.end(0, "engine.wait")
        with pytest.raises(RuntimeError, match="nesting"):
            rec.end(1, "never_opened")

    def test_disabled_recorder_records_nothing(self):
        rec = TraceRecorder(enabled=False)
        rec.begin(0, "tick")
        rec.end(0, "wrong_name")     # no state -> no nesting check either
        assert rec.events() == []


# ============================================== KV floors (obs.cost, no jit)
class TestKVFloors:
    @pytest.mark.parametrize("kv_scheme", ["fp4.25-e2m2", "fp4.5-e2m2",
                                           "fp4.33-e2m2"])
    @pytest.mark.parametrize("hd", [32, 64, 128])
    @pytest.mark.parametrize("kv", [1, 2, 4])
    def test_format_floor_equals_pool_layout(self, kv, hd, kv_scheme):
        # obs.cost derives the floor from scheme params WITHOUT importing
        # repro.cache; the pool derives it from the packed page layout.
        # They must agree per vector at every geometry — drift in either
        # module lands here.
        ccfg = CacheConfig(kind="paged_ams", page_size=PAGE,
                           kv_scheme=kv_scheme)
        per_vec = kv_vector_bytes_floor(hd, get_scheme(kv_scheme))
        assert 2 * kv * per_vec == pool_bytes_per_token(kv, hd, ccfg)

    def test_ideal_floor_convergence(self):
        # fp4.25-e2m2: padding + word granularity vanish at hd=128 —
        # the format floor IS the paper floor there
        fmt = get_scheme("fp4.25-e2m2")
        assert kv_vector_bytes_floor(128, fmt) == \
            kv_vector_bytes_ideal(128, fmt) == 72.0
        # and the overhead at reduced dims is the documented ratio
        assert kv_vector_bytes_floor(32, fmt) / \
            kv_vector_bytes_ideal(32, fmt) == pytest.approx(8 / 7)
        assert kv_vector_bytes_floor(64, fmt) / \
            kv_vector_bytes_ideal(64, fmt) == pytest.approx(40 / 38)

    def test_bf16_cache_floor(self):
        from repro.configs import get_config
        cfg = get_config(ARCH).reduced()
        cm = build_cost_model(cfg, "fp16")   # no cache cfg -> bf16 KV
        per_tok = 2 * cfg.num_kv_heads * cfg.head_dim * 2
        assert cm.kv_bytes_per_token == cfg.num_layers * per_tok
        assert cm.kv_bytes_per_token == cm.kv_bf16_bytes_per_token

    def test_tick_floor_accounting(self):
        from repro.configs import get_config
        cfg = get_config(ARCH).reduced()
        cm = build_cost_model(cfg, SCHEME,
                              CacheConfig(kind="paged_ams", page_size=PAGE))
        assert cm.tick_floor_bytes(0, 0) == cm.weight_bytes   # weights always
        extra = cm.tick_floor_bytes(2, 10) - cm.weight_bytes
        assert extra == 12 * cm.kv_bytes_per_token
        assert cm.tick_floor_flops(2, 10) == \
            2 * cm.flops_per_token + 10 * cm.attn_flops_per_pos
        assert cm.step_time_floor_s(2, 10) > 0


# =========================================================== engine-coupled
def prompts():
    """Four prompts over a shared 16-token prefix."""
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(0, 512, PREFIX)
    return [np.concatenate([sys_prompt, rng.integers(0, 512, n)])
            for n in (5, 3, 7, 2)]


def schedule(stop_id):
    """Mixed traffic over a shared 16-token prefix: more requests than
    slots (queueing), greedy + sampled + stop-token streams (variable
    length, both finish reasons), arrivals timed so no tick is idle.
    ``stop_id`` is the 4th token of the last prompt's greedy stream (the
    `stop_id` fixture): greedy streams are batch-invariant, so that
    request stops by the 4th token with finish_reason "stop"."""
    p = prompts()
    return [
        (0, p[0], SamplingParams(max_tokens=6)),
        (0, p[1], SamplingParams(max_tokens=8)),
        (2, p[2], SamplingParams(temperature=0.9, top_p=0.9, seed=11,
                                 max_tokens=6)),
        (3, p[3], SamplingParams(max_tokens=10, stop_token_ids=(stop_id,))),
    ]


def drive(eng, work):
    """Submit at each item's arrival tick, step until drained. Returns
    (requests, number of step() calls)."""
    reqs, pending, n_steps = [], list(work), 0
    while pending or eng.has_work:
        while pending and pending[0][0] <= eng.tick:
            _, prompt, sp = pending.pop(0)
            reqs.append(eng.submit(prompt, sampling=sp))
        eng.step()
        n_steps += 1
    assert all(r.done for r in reqs)
    return reqs, n_steps


def make_engine(obs=None, speculate_k=0):
    return ServeEngine(ARCH, scheme=SCHEME, slots=2, capacity=48, seed=0,
                       prefill_chunk=4,
                       speculate_k=speculate_k,
                       drafter="self-full" if speculate_k else "ngram",
                       cache_config=CacheConfig(kind="paged_ams",
                                                page_size=PAGE),
                       obs=obs)


@pytest.fixture(scope="module")
def stop_id():
    """The 4th token of the last prompt's greedy stream, from a first run
    of it alone (a stop id that random weights are sure to draw)."""
    eng = make_engine(obs=ObsConfig(enabled=False))
    toks = eng.submit(prompts()[3], max_tokens=4).result()
    assert len(toks) == 4
    return toks[3]


@pytest.fixture(scope="module")
def traced(stop_id):
    eng = make_engine(obs=ObsConfig(trace=True))
    reqs, n_steps = drive(eng, schedule(stop_id))
    return eng, reqs, n_steps


@pytest.fixture(scope="module")
def spec_traced(stop_id):
    eng = make_engine(obs=ObsConfig(trace=True), speculate_k=3)
    work = [(t, p, SamplingParams(max_tokens=sp.max_tokens))  # all greedy
            for t, p, sp in schedule(stop_id)]
    reqs, n_steps = drive(eng, work)
    return eng, reqs, n_steps


class TestZeroPerturbation:
    def test_streams_and_ticks_identical_with_obs_off(self, traced, stop_id):
        eng, reqs, _ = traced
        off = make_engine(obs=ObsConfig(enabled=False))
        reqs_off, _ = drive(off, schedule(stop_id))
        assert eng.tick == off.tick
        for a, b in zip(reqs, reqs_off):
            assert a.tokens == b.tokens
            assert (a.first_token_tick, a.finish_tick, a.finish_reason) == \
                (b.first_token_tick, b.finish_tick, b.finish_reason)
        assert eng.kv_bytes_per_token() == off.kv_bytes_per_token()

    def test_disabled_obs_stats_are_inert_not_broken(self):
        off = make_engine(obs=ObsConfig(enabled=False))
        s = off.stats()
        assert s["ticks"] == 0 and s["requests_finished"] == 0
        # pure-state values stay real even with telemetry off
        assert s["kv_bytes_per_token"] > 0
        assert off.metrics is NULL_REGISTRY
        assert off.phases.ticks() == []


class TestStatsBackwardCompat:
    def test_stats_pin_bit_identical(self, traced):
        """stats() must reproduce the pre-registry hand-counter math:
        recompute every legacy key from the finished Request objects (in
        finish order — exactly what the old implementation observed) and
        require equality, not approx."""
        eng, reqs, n_steps = traced
        s = eng.stats()
        fin = eng.finished
        assert s["ticks"] == n_steps          # workload has no idle ticks
        assert eng.metrics.value("serve_idle_ticks_total") == 0.0
        assert s["requests_finished"] == len(fin) == len(reqs)
        assert s["tokens_generated"] == sum(r.n_generated for r in fin)
        ttft = np.asarray([r.ttft_ticks for r in fin], np.float64)
        e2e = np.asarray([r.latency_ticks for r in fin], np.float64)
        glen = np.asarray([r.n_generated for r in fin], np.float64)
        assert s["ttft_ticks_mean"] == float(ttft.mean())
        assert s["ttft_ticks_p50"] == float(np.percentile(ttft, 50))
        assert s["ttft_ticks_p99"] == float(np.percentile(ttft, 99))
        assert s["latency_ticks_mean"] == float(e2e.mean())
        assert s["latency_ticks_p50"] == float(np.percentile(e2e, 50))
        assert s["latency_ticks_p99"] == float(np.percentile(e2e, 99))
        assert s["gen_tokens_mean"] == float(glen.mean())
        assert s["stopped_early"] == \
            sum(r.finish_reason == "stop" for r in fin)
        assert s["stopped_early"] >= 1        # the stop-token request hit
        # non-speculative: every emission is one draw
        assert s["tokens_per_step"] == 1.0 and s["accept_rate"] == 0.0
        # prefix cache keys still flow through stats
        assert s["prefix_hit_rate"] > 0 and s["cached_token_frac"] > 0

    def test_live_exposition_matches_stats(self, traced):
        eng, reqs, _ = traced
        s = eng.stats()
        parsed = parse_prom(eng.metrics.exposition())
        assert parsed[("serve_device_steps_total", ())] == float(s["ticks"])
        assert parsed[("serve_requests_finished_total",
                       (("reason", "stop"),))] == float(s["stopped_early"])
        assert parsed[("serve_request_ttft_ticks_count", ())] == len(reqs)
        assert parsed[("sched_requests_submitted_total", ())] == \
            float(len(reqs))
        assert ("alloc_pages_total", (("kind", "shared"),)) in parsed

    def test_ticker_line(self, traced):
        eng, _, _ = traced
        line = ticker_line(eng)
        assert "B/tok" in line and "x floor" in line and "act" in line


class TestSpans:
    def _tracks(self, eng):
        spans = validate_events(eng.trace.events())   # raises on violation
        assert eng.trace.open_spans() == {}           # all closed at drain
        return spans

    def test_request_lifecycle_spans(self, traced):
        eng, reqs, _ = traced
        spans = self._tracks(eng)
        for r in reqs:
            names = [n for n, _, _, _ in spans[r.rid + 1]]
            # one full lifecycle per request track (spans listed in
            # completion order: the request umbrella closes last)
            assert names == ["queued", "prefill", "decode", "request"]
            by = {n: (b, e) for n, b, e, _ in spans[r.rid + 1]}
            assert by["queued"][1] <= by["prefill"][0]
            assert by["prefill"][1] <= by["decode"][0]
            # lifecycle spans nest inside the request umbrella span
            assert by["request"][0] <= by["queued"][0]
            assert by["decode"][1] <= by["request"][1]

    def test_engine_tick_spans(self, traced):
        """Every tick's phases tile it in order with no overlap: contiguous
        inside step_begin and inside step_end, the seam between the two
        halves belonging to the caller; the export nests them in the
        tick's span."""
        eng, _, n_steps = traced
        rec = eng.phases.ticks()
        assert [t for t, _ in rec] == list(range(n_steps))
        names = [PHASE_PREFIX + p for p in BEGIN_PHASES + END_PHASES]
        for _, phases in rec:
            assert [n for n, _, _ in phases] == names
            assert all(a <= b for _, a, b in phases)
            for (_, _, b), (_, a, _) in zip(phases, phases[1:]):
                assert b <= a                              # no overlap
            half = len(BEGIN_PHASES)
            for part in (phases[:half], phases[half:]):    # tiled halves
                assert all(x[2] == y[1] for x, y in zip(part, part[1:]))
        spans = self._tracks(eng)
        ticks = [x for x in spans[0] if x[0] == "tick"]
        kids = [x for x in spans[0] if x[0].startswith(PHASE_PREFIX)]
        assert len(ticks) == n_steps and len(kids) == len(names) * n_steps
        assert all(d == 0 for _, _, _, d in ticks)
        assert all(d == 1 for _, _, _, d in kids)
        for (_, a, b, _), (_, phases) in zip(ticks, rec):
            assert (a, b) == (phases[0][1] / 1e3, phases[-1][2] / 1e3)

    def test_phase_counters_match_record(self, traced):
        """The registry counter and the record are fed by one stamp per
        boundary: per phase, the counter is the record's summed time."""
        eng, _, _ = traced
        rec = eng.phases.ticks()
        for p in BEGIN_PHASES + END_PHASES:
            ns = sum(b - a for _, phases in rec for n, a, b in phases
                     if n == PHASE_PREFIX + p)
            got = eng.metrics.value("serve_tick_phase_seconds_total", phase=p)
            assert got == pytest.approx(ns * 1e-9, rel=1e-9, abs=1e-12)

    def test_phase_record_is_bounded(self, traced):
        assert traced[0].phases.capacity == 4096
        t = {"now": 0}

        def clock():
            t["now"] += 1000
            return t["now"]

        m = MetricsRegistry()
        ph = TickPhases(capacity=8, metrics=m, clock=clock)
        for tick in range(20):
            ph.begin_tick()
            for p in BEGIN_PHASES[1:] + END_PHASES:
                ph.mark(p)
            ph.close_tick(tick)
        rec = ph.ticks()
        assert [tick for tick, _ in rec] == list(range(12, 20))
        assert all(len(phases) == 7 for _, phases in rec)
        # the counters keep every tick, the record only the last eight
        assert m.value("serve_tick_phase_seconds_total", phase="admit") == \
            pytest.approx(20 * 1e-6)

    def test_tracing_never_blocks_on_the_device(self, monkeypatch, stop_id):
        """No span waits for the device: a traced engine serves with
        jax.block_until_ready unusable."""
        def refuse(*_a, **_k):
            raise AssertionError("block_until_ready inside the tick")
        eng = make_engine(obs=ObsConfig(trace=True))
        monkeypatch.setattr(jax, "block_until_ready", refuse)
        reqs, _ = drive(eng, schedule(stop_id)[:2])
        assert all(r.done for r in reqs) and eng.phases.ticks()

    def test_request_wall_clock_stamps(self, traced):
        eng, reqs, _ = traced
        for r in reqs:
            assert 0 < r.submit_ns <= r.admit_ns <= r.first_token_ns \
                <= r.finish_ns
        fin = eng.finished
        parsed = parse_prom(eng.metrics.exposition())
        assert parsed[("serve_request_ttft_seconds_count", ())] == len(fin)
        assert parsed[("serve_queue_wait_seconds_count", ())] == len(fin)
        assert parsed[("serve_request_ttft_seconds_sum", ())] == \
            pytest.approx(sum(r.first_token_ns - r.submit_ns
                              for r in fin) * 1e-9)


    def test_spans_survive_speculative_rollback(self, spec_traced):
        """Speculative traffic (drafts scored + rolled back in-step,
        multi-token emission rounds, early finishes freeing slots
        mid-tick) must not bend the span lifecycle."""
        eng, reqs, _ = spec_traced
        spans = self._tracks(eng)
        s = eng.stats()
        assert s["spec_proposed"] > 0
        assert 0 < s["spec_accepted"] <= s["spec_proposed"]
        assert s["tokens_per_step"] > 1.0     # speculation actually paid
        for r in reqs:
            names = [n for n, _, _, _ in spans[r.rid + 1]]
            assert names == ["queued", "prefill", "decode", "request"]

    def test_spec_streams_unchanged_by_telemetry(self, spec_traced, stop_id):
        eng, reqs, _ = spec_traced
        off = make_engine(obs=ObsConfig(enabled=False), speculate_k=3)
        work = [(t, p, SamplingParams(max_tokens=sp.max_tokens))
                for t, p, sp in schedule(stop_id)]
        reqs_off, _ = drive(off, work)
        for a, b in zip(reqs, reqs_off):
            assert a.tokens == b.tokens


BENCHMARK_SPANS = {"submit", "step_begin", "step_end", "collect"}


@pytest.fixture(scope="module")
def profiled(tmp_path_factory, stop_id):
    """One engine run inside a JAX profiler session: the capture's host
    events and the engine's own phase record."""
    from jax.profiler import ProfileData
    d = str(tmp_path_factory.mktemp("prof"))
    eng = make_engine()
    drive(eng, schedule(stop_id)[:1])          # compile outside the capture
    n0 = len(eng.phases.ticks())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        drive(eng, schedule(stop_id)[:2])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    start = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    host = [(e.name, e.start_ns, e.end_ns)
            for line in pd.find_plane_with_name("/host:CPU").lines
            for e in line.events]
    return eng.phases.ticks()[n0:], start, host


class TestProfilerClock:
    def test_phases_sit_on_the_profiler_clock(self, profiled):
        """Each phase is a profiler annotation of its own name, and its
        stamps minus the session's profile_start_time are the capture's
        times for it: the record and the trace share one clock."""
        rec, start, host = profiled
        ann = sorted((e for e in host if e[0].startswith(PHASE_PREFIX)),
                     key=lambda e: e[1])
        flat = [p for _, phases in rec for p in phases]
        assert [n for n, _, _ in ann] == [n for n, _, _ in flat]
        for (n, a, b), (_, ta, tb) in zip(flat, ann):
            assert abs((a - start) - ta) < 2e6        # ns: within 2 ms
            assert abs((b - start) - tb) < 2e6

    def test_no_program_span_takes_a_benchmark_name(self, profiled, traced):
        _, _, host = profiled
        eng, _, _ = traced
        names = {n for n, _, _ in host}
        names |= {n for _, phases in eng.phases.ticks() for n, _, _ in phases}
        names |= {e["name"] for e in eng.trace.events()}
        assert any(n.startswith(PHASE_PREFIX) for n in names)
        assert not names & BENCHMARK_SPANS


class TestRoofline:
    def test_measured_kv_bytes_within_floor_tolerance(self, traced):
        """The acceptance bar: measured paged-AMS bytes/token vs the
        independently derived analytic floor, within 10%. (It is in fact
        EXACT — any non-1.0 ratio is a layout change in pool or cost.)"""
        eng, _, _ = traced
        s = eng.stats()
        assert abs(s["kv_floor_ratio"] - 1.0) <= 0.10
        assert s["kv_floor_ratio"] == 1.0
        assert s["kv_bytes_per_token"] == s["kv_bytes_per_token_floor"]
        # reduced dims (hd=32): the ideal/paper floor gap is the padding
        assert s["kv_vs_ideal_floor"] == pytest.approx(8 / 7)

    def test_attribution_report(self, traced):
        eng, _, _ = traced
        rep = attribution(eng)
        s = eng.stats()
        assert rep["signature"]["cache"] == "paged_ams"
        assert rep["signature"]["chunk"] == 4
        assert rep["served_ticks"] == s["ticks"]
        # read amplification: the ref paged gather reads whole pages, so
        # achieved KV traffic strictly exceeds the causal floor
        assert rep["kv_achieved_vs_floor"] > 1.0
        assert rep["kv_achieved_vs_floor"] == s["kv_achieved_vs_floor"]
        # floors accumulate: weights are re-read every tick at minimum
        cm = eng.cost_model
        assert rep["floor_hbm_bytes_total"] >= \
            rep["served_ticks"] * cm.weight_bytes
        assert rep["floor_flops_total"] > 0
        # per-request attribution landed on the Request objects
        assert all(r.kv_vs_floor > 1.0 for r in eng.finished)

    def test_hlo_cost_attribution(self, traced):
        """--hlo-cost path: lower + compile the live step and parse XLA's
        own cost — the achieved program must cost at least something and
        report a finite ratio vs the analytic floor."""
        eng, _, _ = traced
        rep = attribution(eng, hlo=True)
        assert rep["hlo_flops_per_tick"] > 0
        assert rep["hlo_hbm_bytes_per_tick"] > 0
        assert rep["hlo_hbm_vs_floor"] > 0


class TestObsConfig:
    def test_validation(self):
        c = ObsConfig(enabled=False, trace=True, cost=True)
        assert not c.trace_on and not c.cost_on   # master switch wins
