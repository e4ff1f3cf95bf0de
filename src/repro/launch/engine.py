"""Continuous-batching serving engine over the AMS-quantized model.

This is the paper's deployment story made a serving hot path instead of a
fixed-batch benchmark loop: weights are AMS-quantized/packed ahead of time
(`QuantPolicy` -> `quantize_params`), and ONE jitted slot-masked decode step
(`launch.steps.build_engine_step`) then serves every in-flight request per
tick, streaming the packed planes through `apply_linear`'s ``ref`` /
``fused_ref`` / ``pallas_interpret`` impls.

Architecture (Orca-style iteration-level scheduling):

  * the KV cache is either a fixed [slots, capacity] tensor (contiguous
    mode) or a POOL of fixed-size pages addressed through per-request
    block tables (`repro.cache`, paged-bf16 / paged-AMS modes — the AMS
    pool stores each K/V vector in the paper's packed e2m2 planes,
    quantized once at insert). Each slot holds one request with its own
    position counter (`decode_step` takes [B] per-slot positions;
    negative = idle slot, cache write suppressed);
  * a priority scheduler (`launch.scheduler`; all-default priorities =
    strict FIFO) admits queued requests into freed slots; admission is
    capacity-checked at submit time (contiguous) or gated on the free-PAGE
    budget at admit time (paged — short requests reserve only their own
    pages, not worst-case slots);
  * PREEMPTION (paged modes, ``EngineConfig.preempt``): when the queue
    head outranks the lowest-priority active request and cannot admit, the
    engine preempts that victim — its private pages' content spills to
    host memory in the pool's PACKED layout (`cache.pool.extract_pages`;
    AMS planes byte-exact), its shared prefix pages stay pinned
    (refcounts held), and it re-queues ahead of its priority class. On
    re-admission the engine restores the spilled pages into fresh device
    pages and resumes feeding at the exact spilled position — never
    re-prefilling — so preempted streams are bit-identical to
    uninterrupted ones (seeded draws fold only (rid, token index), never
    slot or tick). Below eviction sits the optional host spill tier
    (`CacheConfig.host_spill_pages`): LRU-evicted published pages offload
    host-side and restore on a later prefix hit instead of re-prefilling;
  * completed PROMPT pages are PREFIX-CACHED across requests (paged modes,
    on by default; ``CacheConfig(prefix_cache=False)`` disables): each full
    prompt page is content-addressed by a prefix-chain hash, and a request
    whose prompt shares a cached page-aligned prefix references the SAME
    physical pages (refcounted, read-only) and starts prefill at the cached
    length — a shared 1k-token system prompt prefills once, not once per
    request. Admission charges only the uncached page count; refcount-0
    cached pages stay resident in an LRU until memory pressure evicts them.
    Reuse is bit-exact because the pool's insert quantization is
    deterministic per (token, head);
  * prefill is CHUNKED INTO THE DECODE BATCH as a RAGGED MULTI-TOKEN STEP:
    each tick, every active slot contributes a variable-length block of up
    to ``prefill_chunk`` tokens — prefilling slots consume a prompt chunk
    (and any modality prefix embeddings), decoding slots consume 1 — all
    executed as ONE jitted program (`launch.steps.build_engine_step` with
    ``chunk=C``). Logits are taken in-step at each slot's last valid
    token, so time-to-first-token scales with ceil(prompt/C) ticks instead
    of prompt length. A global per-tick TOKEN BUDGET caps the chunk total;
    every active slot is guaranteed one token per tick and admission is
    budget-aware (`FIFOScheduler.admit(max_admit=...)`), so a long prefill
    can never starve decode slots. One program, no separate prefill
    compilation, no batch-shape churn. (``prefill_chunk=1`` — the default,
    and the only mode for recurrent-state families — degenerates to the
    original one-position-per-tick step.);
  * sampling is ON-DEVICE and PER-REQUEST (`repro.launch.sampling`): each
    request carries a `SamplingParams(temperature, top_k, top_p, seed,
    max_tokens, stop_token_ids)`; the step applies the logit transforms
    and categorical draw from per-slot folded PRNG keys and decides
    termination (stop-token hit or length cap) in-step, so only [B] int32
    tokens + [B] done bools cross to the host per tick. ``temperature=0``
    (the default) lowers to the exact argmax path, keeping every greedy
    stream-equivalence guarantee bit-identical. Seeded streams replay
    bit-identically across engine restarts and slot reassignment: the
    draw key folds in the REQUEST id and the request's own token index,
    never the slot or tick. A finished slot frees its pages (prefix pages
    stay published per the refcount semantics above) and the queue is
    re-polled the SAME tick, so early EOS turns directly into admission
    headroom;
  * SPECULATIVE DECODING rides the same ragged step
    (`launch.speculative`, ``speculate_k=k`` + ``drafter``): on
    pure-decode rounds a cheap host drafter proposes up to k tokens per
    slot, the step feeds ``[last_token, d_1..d_k]`` so ONE pass scores
    every draft, and an on-device verify epilogue accepts the longest
    correct prefix, draws the bonus/corrective token, terminates in-step,
    and zero-scatters rejected KV entries back to pool-initial state —
    the engine then rewinds its feed position (never past the prompt, so
    shared prefix pages are structurally untouchable). Greedy streams
    stay bit-identical to non-speculative decoding; a round emits 1..k+1
    tokens per model pass (``stats()``: ``accept_rate`` /
    ``tokens_per_step``). See docs/speculative.md;
  * OBSERVABILITY is first-class (`repro.obs`, ``obs=ObsConfig(...)``):
    the engine owns a metrics registry every subsystem (scheduler,
    allocator, drafter) emits into, and ``stats()`` is computed from it
    (bit-identical to the historical hand counters); every tick is split
    into phases stamped on the JAX profiler's clock (`obs.trace.TickPhases`:
    a bounded record, a counter per phase, a profiler annotation per
    phase); ``ObsConfig(trace=True)`` also keeps per-request lifecycle
    spans and the tick phases as a Perfetto-loadable Chrome trace, and the
    roofline cost model
    (`obs.cost`) attributes analytic floor HBM bytes/FLOPs to every tick
    and request. ``ObsConfig(enabled=False)`` swaps in no-op instruments —
    telemetry cannot perturb the measured system. See
    docs/observability.md.

Because every slot's computation is row-independent (attention hard-masks
invalid cache positions to exact zeros), a request's token stream is
identical whether it runs alone or packed against arbitrary neighbours —
``tests/test_engine.py`` pins this batch-invariance against the one-shot
``launch.serve.generate`` path. (MoE configs are the exception: capacity-
based expert routing couples tokens across the batch.)

Quickstart (the stable facade is `repro.serving`)::

    from repro.serving import EngineConfig, ServeEngine

    eng = ServeEngine(EngineConfig(arch="qwen2-7b", scheme="fp5.33-e2m3",
                                   slots=4, capacity=64))
    handle = eng.submit(np.array([1, 2, 3]), max_tokens=16)
    print(handle.result())

The legacy keyword constructor (``ServeEngine("qwen2-7b", slots=4, ...)``)
still works via `EngineConfig.from_legacy` with a DeprecationWarning, and
is pinned to an identical `engine_step_signature`. The driver loop can be
a plain ``eng.run()``, a per-handle ``handle.result()``, or the asyncio
HTTP/SSE front end (`repro.launch.frontend`) which overlaps host-side
request intake/streaming with the device step via `step_begin`/`step_end`.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import (
    CacheConfig,
    PageAllocator,
    compression_vs_bf16,
    extract_pages,
    host_bytes,
    prefix_page_hashes,
    restore_pages,
)
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core.policy import QuantPolicy
from repro.launch.config import EngineConfig
from repro.launch.mesh import make_driver_mesh
from repro.launch.sampling import (
    GREEDY,
    SamplingParams,
    clear_slot,
    fill_slot,
    request_key,
    slot_batch,
)
from repro.launch.scheduler import (
    DECODE,
    FINISHED,
    PREEMPTED,
    PREFILL,
    FIFOScheduler,
    Request,
    SpilledState,
)
from repro.launch.steps import build_engine_step, engine_step_signature
from repro.models import (
    cast_float_weights,
    init_serving_params,
    make_cache,
    model_dims,
    quantize_params,
    reset_cache_slot,
)
from repro.obs import (
    MetricsRegistry,
    ObsConfig,
    TickPhases,
    TraceRecorder,
    build_cost_model,
    now_ns,
)
from repro.obs.metrics import (
    COUNT_BUCKETS,
    LATENCY_BUCKETS,
    NULL_REGISTRY,
    TIME_BUCKETS,
)


class RequestHandle:
    """Client-facing view of a submitted request — the ONLY object
    `ServeEngine.submit` returns. It exposes the stable read surface
    (`.status`, `.tokens_so_far()`, `.result()`, async `.stream()`) and
    transparently forwards every other attribute read to the underlying
    `Request` record, so existing code that inspected `.tokens`, `.done`,
    `.ttft_ticks`, ... keeps working while new code never touches Request
    internals."""

    __slots__ = ("_req", "_eng")

    def __init__(self, req: Request, engine: "ServeEngine"):
        object.__setattr__(self, "_req", req)
        object.__setattr__(self, "_eng", engine)

    @property
    def request(self) -> Request:
        """The underlying scheduler record (escape hatch; internals)."""
        return self._req

    @property
    def engine(self) -> "ServeEngine":
        """The engine serving this request (read-only)."""
        return self._eng

    @property
    def status(self) -> str:
        """Lifecycle: queued -> prefill -> decode -> finished, with
        preempted as the spilled-out detour (scheduler.REQUEST_STATUSES)."""
        return self._req.status

    @property
    def done(self) -> bool:
        return self._req.done

    def tokens_so_far(self) -> List[int]:
        """Snapshot of the tokens generated so far (a copy)."""
        return list(self._req.tokens)

    def result(self, max_ticks: int = 1_000_000) -> List[int]:
        """Block until this request finishes and return its full token
        list. When no driver loop is running this drives the engine
        itself; when one is (``engine.driver_active``, e.g. the async
        frontend), it waits on the engine's tick signal instead."""
        eng, req = self._eng, self._req
        for _ in range(max_ticks):
            if req.done:
                break
            if eng.driver_active:
                eng.wait_tick(eng.tick)
            elif eng.has_work:
                eng.step()
            else:          # pragma: no cover - submitted but queue dropped
                break
        return list(req.tokens)

    async def stream(self):
        """Async token stream (the SSE feed): yields each generated token
        id as it lands, finishing when the request does. Drives the engine
        from a worker thread when no driver loop is active; otherwise
        waits on the engine's tick signal so any number of streams ride
        one driver."""
        import asyncio
        eng, req = self._eng, self._req
        sent = 0
        while True:
            while sent < len(req.tokens):
                tok = int(req.tokens[sent])
                sent += 1
                yield tok
            if req.done:
                return
            if eng.driver_active:
                await asyncio.to_thread(eng.wait_tick, eng.tick)
            else:
                await asyncio.to_thread(eng.step)

    def __getattr__(self, name):
        return getattr(self._req, name)

    def __repr__(self):
        r = self._req
        return (f"RequestHandle(rid={r.rid}, status={r.status!r}, "
                f"tokens={len(r.tokens)})")


@dataclasses.dataclass
class _PendingStep:
    """In-flight device step between `step_begin` and `step_end` (the
    double-buffering seam: the host is free while the device computes)."""

    outs: Any                 # un-awaited step outputs (async dispatch)
    nvalid: np.ndarray
    ndraft: np.ndarray
    t0: float
    fed: int
    tracing: bool
    idle: bool = False
    result: Optional[Dict[str, object]] = None   # idle ticks resolve early


class ServeEngine:
    """Slot-based continuous-batching engine (see module docstring)."""

    def __init__(self, config: Any = None, *, params=None, **legacy):
        # THE constructor surface is one frozen EngineConfig (every
        # validation already ran in its __post_init__ — the single error
        # surface). The pre-redesign keyword form ServeEngine(arch,
        # slots=..., ...) routes through the from_legacy deprecation shim,
        # pinned to an identical engine_step_signature. `params` stays a
        # direct argument: it is runtime state (weights), not config.
        if isinstance(config, EngineConfig):
            if legacy:
                raise TypeError(
                    f"ServeEngine(EngineConfig, ...) takes no extra "
                    f"keyword arguments, got {sorted(legacy)}")
            ec = config
        else:                     # legacy: positional arch string (or None)
            ec = EngineConfig.from_legacy(config, **legacy)
        self.config = ec
        cfg = get_config(ec.arch)
        if ec.reduced:
            cfg = cfg.reduced()
        self.cfg = cfg
        scheme = self.scheme = ec.scheme
        slots = self.slots = ec.slots
        capacity = self.capacity = ec.capacity
        self.chunk = ec.prefill_chunk   # chunk support is gated by
        #                              build_engine_step(check_chunked_support)
        self.speculate_k = ec.speculate_k
        # the jitted step's chunk width must hold 1 fed token + k drafts
        # per slot; prefill growth stays capped at prefill_chunk
        self.step_chunk = ec.step_chunk
        # per-tick token budget: every active slot is guaranteed 1; prefill
        # chunks and draft blocks grow only into the leftover. Default = no
        # throttling.
        self.token_budget = ec.resolved_token_budget
        ccfg = self.cache_cfg = ec.sized_cache()
        # preemption needs pages to spill — contiguous caches run the
        # PR 1-9 no-preemption policy regardless of the flag
        self.preempt_enabled = bool(ec.preempt and ccfg.paged)
        # observability (repro.obs): one registry per engine, resolved to
        # the shared no-op instruments when disabled — recording can never
        # perturb the measured system (bench --obs-check asserts 0% drift)
        self.obs = ec.obs
        self.metrics = (MetricsRegistry() if self.obs.enabled
                        else NULL_REGISTRY)
        self.trace = TraceRecorder(enabled=self.obs.trace_on)
        self.trace.thread(0, "engine")
        self.phases = TickPhases(enabled=self.obs.enabled,
                                 metrics=self.metrics)
        quant = None
        if scheme != "fp16":
            quant = QuantPolicy(scheme=scheme, strategy=ec.strategy,
                                impl=ec.impl, min_elements=1 << 10)
        self.rcfg = RunConfig(model=cfg, seq_len=capacity, global_batch=slots,
                              mode="decode", quant=quant)
        # tensor-parallel serving: pass an explicit mesh (e.g.
        # mesh.make_serving_mesh(tp)) and the jitted step runs sharded —
        # weight planes placed by the serving layout, paged pools
        # head-sharded over the model axis, token streams bit-identical to
        # the single-device engine. Default: the mesh_kind driver mesh
        # (1x1 for "none").
        self.mesh = ec.mesh if ec.mesh is not None \
            else make_driver_mesh(ec.mesh_kind)
        seed, drafter, verbose = ec.seed, ec.drafter, ec.verbose

        with jax.set_mesh(self.mesh):
            tp = self.mesh.shape["model"]
            # arg shapes are kept for obs.cost.hlo_step_cost: lowering the
            # jitted step at its serving shapes yields the compiled
            # program's achieved per-tick HBM/FLOP cost
            self._step, self._step_shapes, _shardings = build_engine_step(
                self.mesh, cfg, self.rcfg, cache_cfg=ccfg,
                chunk=self.step_chunk, sampling=True,
                speculate_k=self.speculate_k)
            t0 = time.time()
            if params is None:
                # one layer group at a time: the float tree never exists
                params = init_serving_params(jax.random.PRNGKey(seed), cfg,
                                             tp=tp, quant=quant)
            else:
                # float weights to bf16 and (re)quantize; packed linears
                # pass through untouched
                params = cast_float_weights(params)
                if quant is not None:
                    params = quantize_params(params, quant)
            if verbose and quant is not None:
                print(f"[ptq] quantized to {ec.scheme} ({ec.strategy}) "
                      f"in {time.time()-t0:.1f}s", flush=True)
            # the jitted step's in_shardings expect exactly this placement
            self.params = params = jax.device_put(params, _shardings["params"])
            # the CacheConfig threads through for contiguous caches too:
            # its impl field routes the decode cores through the fused
            # attention template (kernels.attention_template)
            self.cache = jax.jit(
                lambda: make_cache(cfg, slots, capacity, tp=tp,
                                   dtype=jnp.bfloat16, cache_cfg=ccfg),
                out_shardings=_shardings["cache"])()
            # per-device KV residency: a head-sharded paged pool (kv heads
            # divide the model axis — the same rule steps.py/pool_shardings
            # apply) keeps 1/tp of the pool on each device, so all
            # kv-bytes-per-token accounting below is PER DEVICE
            _dims = model_dims(cfg, tp)
            self._kv_shards = (tp if (ccfg.paged and tp > 1
                                      and _dims.kv % tp == 0) else 1)
            # host->device spill restores happen OUTSIDE the jitted step;
            # on a tp>1 mesh the restored cache must be re-placed to the
            # step's expected sharding before the next dispatch
            self._cache_sharding = _shardings.get("cache")
            # the drafter proposes from the (possibly quantized) serving
            # params — resolved here so "self" binds the engine's own stack
            self.drafter = None
            if self.speculate_k:
                from repro.launch.speculative import Drafter, make_drafter
                if isinstance(drafter, str):
                    drafter = make_drafter(drafter, params=params, cfg=cfg,
                                           capacity=capacity, tp=tp,
                                           policy=quant)
                if not isinstance(drafter, Drafter):
                    raise TypeError(f"drafter must be a Drafter or name, "
                                    f"got {type(drafter).__name__}")
                self.drafter = drafter
                self.drafter.bind_metrics(self.metrics)
            # paged pools need no per-slot reset: positions are written
            # front-to-front per request, so every valid key is fresh, and
            # recurrent-state families are rejected by check_paged_support
            self._reset = (None if ccfg.paged else
                           jax.jit(reset_cache_slot, donate_argnums=(0,)))

        # host-side slot state
        if ccfg.paged:
            self.alloc: Optional[PageAllocator] = PageAllocator(
                ccfg.num_pages, ccfg.page_size, metrics=self.metrics,
                host_spill_pages=ccfg.host_spill_pages)
            # the eviction-spill hook: reads the CURRENT cache pytree at
            # eviction time (self.cache rebinds functionally every tick)
            self.alloc.spill_fn = \
                lambda page: extract_pages(self.cache, [page])
            self.block_tables = np.zeros(
                (slots, ccfg.max_pages_per_seq), np.int32)
            # a request can never outgrow its block-table row or the pool
            eff_cap = min(ccfg.max_pages_per_seq, ccfg.num_pages) * ccfg.page_size
        else:
            self.alloc = None
            self.block_tables = None
            eff_cap = capacity
        self.sched = FIFOScheduler(eff_cap, max_queue=ec.max_queue,
                                   metrics=self.metrics)
        self.active: List[Optional[Request]] = [None] * slots
        self.fed = np.zeros(slots, np.int32)   # inputs consumed == insert pos
        self.last_token = np.zeros(slots, np.int32)
        # per-slot sampling state shipped to the step each tick (key, ngen,
        # temperature, top_k, top_p, max_tokens, stop_ids rows)
        self.samp = slot_batch(slots)
        self.tick = 0
        self.finished: List[Request] = []
        self._rid = itertools.count()
        # preemption accounting (plain ints: real state, registry-
        # independent, like PageAllocator.hits)
        self.preemptions = 0       # requests preempted (spilled out)
        self.resumes = 0           # preempted requests re-admitted
        self.spill_pages = 0       # pages whose content spilled host-side
        self.spill_bytes = 0       # host bytes those spills occupied
        # double-buffered dispatch seam: at most ONE device step in flight
        self._pending: Optional[_PendingStep] = None
        # tick signal for concurrent waiters (RequestHandle.result/stream
        # under an external driver loop, e.g. the async frontend)
        self._tick_cv = threading.Condition()
        self.driver_active = False
        # serializes frontend-thread submit() against the driver thread's
        # admission/preemption pass (RLock: _admit -> preempt -> requeue)
        self._queue_lock = threading.RLock()

        # --- telemetry instruments, resolved ONCE (recording on the tick
        # path is then a plain float add; all of stats() derives from
        # these — the two tick histograms keep raw observations in
        # insertion order so the legacy percentile math is bit-identical)
        m = self.metrics
        self.signature = engine_step_signature(
            cfg, self.rcfg, cache_cfg=ccfg,
            chunk=self.step_chunk, speculate_k=self.speculate_k,
            mesh=self.mesh)
        m.gauge("serve_step_signature_info",
                "engine-step signature (value is always 1)",
                tuple(self.signature)).labels(**self.signature).set(1)
        self._m_tick_s = m.histogram(
            "serve_tick_seconds", "wall seconds per served (non-idle) tick",
            buckets=TIME_BUCKETS)
        self._m_tick_tok = m.histogram(
            "serve_tick_tokens", "tokens emitted per served tick",
            buckets=COUNT_BUCKETS)
        self._m_idle = m.counter("serve_idle_ticks_total",
                                 "ticks with no active slot")
        self._m_steps = m.counter("serve_device_steps_total",
                                  "jitted engine-step invocations")
        self._m_fed = m.counter("serve_tokens_fed_total",
                                "input positions fed through the step")
        # the Pallas paged kernel walks (slot, page) cells of every layer's
        # block table and stops at each slot's length: attended cells run
        # the body, skipped ones fetch and attend nothing
        pages = m.counter("serve_paged_attn_pages_total",
                          "(slot, page, layer) cells of the paged attention "
                          "grid, by kind", ("kind",))
        self._m_pages_att = pages.labels(kind="attended")
        self._m_pages_skip = pages.labels(kind="skipped")
        self._paged_attn_layers = (
            cfg.num_layers if ccfg.paged and ccfg.impl != "ref" else 0)
        self._m_chunk = m.histogram(
            "serve_chunk_tokens", "tokens fed per active slot per tick",
            buckets=COUNT_BUCKETS, keep_raw=False)
        self._m_finished = m.counter("serve_requests_finished_total",
                                     "finished requests, by reason",
                                     ("reason",))
        self._m_fin_stop = self._m_finished.labels(reason="stop")
        self._m_fin_len = self._m_finished.labels(reason="length")
        self._m_prompt = m.counter("serve_prompt_tokens_total",
                                   "prompt positions admitted")
        self._m_cached = m.counter("serve_cached_prompt_tokens_total",
                                   "prompt positions served from shared pages")
        self._m_preempt = m.counter("serve_preemptions_total",
                                    "requests preempted (pages spilled)")
        self._m_resume = m.counter("serve_resumes_total",
                                   "preempted requests re-admitted")
        self._m_spill_pages = m.counter(
            "serve_spill_pages_total",
            "private pages spilled host-side at preemption")
        self._m_restore_pages = m.counter(
            "serve_restore_pages_total",
            "spilled pages restored into fresh device pages")
        self._m_spill_bytes = m.counter(
            "serve_spill_bytes_total",
            "host bytes occupied by preemption spills")
        self._m_spec_prop = m.counter("serve_spec_proposed_total",
                                      "draft tokens scored by the step")
        self._m_spec_acc = m.counter("serve_spec_accepted_total",
                                     "draft tokens accepted by the verify")
        self._m_emit = m.counter("serve_emit_rounds_total",
                                 "slot-rounds that emitted tokens")
        self._m_ttft = m.histogram("serve_request_ttft_ticks",
                                   "submit -> first token, engine ticks",
                                   buckets=COUNT_BUCKETS)
        self._m_lat = m.histogram("serve_request_latency_ticks",
                                  "submit -> finish, engine ticks",
                                  buckets=COUNT_BUCKETS)
        self._m_ttft_s = m.histogram(
            "serve_request_ttft_seconds",
            "submit -> first token on the host, wall seconds",
            buckets=LATENCY_BUCKETS, keep_raw=False)
        self._m_wait_s = m.histogram(
            "serve_queue_wait_seconds",
            "submit -> first admission, wall seconds",
            buckets=LATENCY_BUCKETS, keep_raw=False)
        self._m_glen = m.histogram("serve_request_gen_tokens",
                                   "tokens generated per finished request",
                                   buckets=COUNT_BUCKETS)
        self._m_active = m.gauge("serve_active_slots",
                                 "slots serving a request")
        m.gauge("serve_queue_depth", "requests waiting for a slot",
                fn=lambda: self.sched.queue_depth)

        # --- roofline attribution (obs.cost): analytic floors for this
        # step signature; per-tick accounting runs in step()
        self.cost_model = None
        if self.obs.cost_on:
            dims = model_dims(cfg, self.mesh.shape["model"])
            self.cost_model = build_cost_model(
                cfg, scheme, ccfg,
                kv=dims.kv, hd=dims.hd, tp=self.mesh.shape["model"],
                kv_shards=self._kv_shards, signature=self.signature)
            self._kv_bpt = float(self.kv_bytes_per_token())
            self._m_floor_b = m.counter(
                "serve_floor_hbm_bytes_total",
                "analytic floor HBM bytes (weights + causal KV)")
            self._m_floor_f = m.counter("serve_floor_flops_total",
                                        "analytic floor FLOPs")
            self._m_kv_floor = m.counter(
                "serve_kv_floor_bytes_total",
                "causal-floor KV bytes (writes + attended reads)")
            self._m_kv_ach = m.counter(
                "serve_kv_achieved_bytes_total",
                "KV bytes the cache implementation touches")

    # ------------------------------------------------------------- frontend
    def submit(self, prompt, max_tokens: Optional[int] = None,
               prefix_embeds=None,
               sampling: Optional[SamplingParams] = None,
               priority: int = 0) -> RequestHandle:
        """Enqueue a request and return its `RequestHandle` (`.status`,
        `.tokens_so_far()`, `.result()`, async `.stream()`). Raises if it
        can never fit a cache slot. (`Request.__post_init__` normalizes
        the prompt to [P] int32.)

        ``sampling`` configures the per-request draw (temperature/top_k/
        top_p/seed) and termination (stop_token_ids + max_tokens); omitted
        -> greedy argmax, exactly the PR 1-4 behaviour. ``max_tokens`` is
        the length CAP — ``sampling.max_tokens`` wins when both are given,
        and a stop-token hit ends the stream earlier. ``priority`` (higher
        = more urgent, default 0) orders the queue and — paged modes with
        ``EngineConfig.preempt`` — lets a blocked high-priority head spill
        a lower-priority active request out to host memory."""
        sp = sampling if sampling is not None else GREEDY
        if sp.max_tokens is not None:
            max_tokens = sp.max_tokens
        if max_tokens is None:
            raise ValueError(
                "max_tokens required (argument or SamplingParams.max_tokens)")
        if prefix_embeds is not None:
            prefix_embeds = np.asarray(prefix_embeds, np.float32)
            if self.cfg.num_prefix_embeds == 0:
                raise ValueError(
                    f"{self.cfg.name} has no modality frontend; "
                    "prefix_embeds unsupported")
            if (prefix_embeds.ndim != 2
                    or prefix_embeds.shape[1] != self.cfg.d_model):
                raise ValueError(
                    f"prefix_embeds must be [n, d_model={self.cfg.d_model}], "
                    f"got {prefix_embeds.shape}")
        # the queue lock serializes frontend-thread submissions against the
        # driver thread's admission pass (heap push vs pop)
        with self._queue_lock:
            rid = next(self._rid)
            # request-level PRNG key: seed + REQUEST id (never the
            # slot/tick), so seeded streams replay across restarts and
            # slot reassignment
            req = Request(rid=rid, prompt=prompt, max_tokens=max_tokens,
                          prefix_embeds=prefix_embeds, sampling=sp,
                          key_data=request_key(sp.seed, rid),
                          priority=priority, submit_ns=now_ns())
            ccfg = self.cache_cfg
            if ccfg.paged and ccfg.prefix_cache and prefix_embeds is None:
                # chain hash per FULL prompt page — the prefix-cache
                # identity (modality prefixes are request-local floats, not
                # hashable token pages, so VLM/audio requests skip the
                # cache)
                req.page_hashes = prefix_page_hashes(
                    req.prompt, ccfg.page_size, ccfg.content_key)
            self.sched.submit(req, self.tick)     # raises on backpressure
            if self.trace.enabled:
                # one trace thread per request (tid 0 is the engine): the
                # request span opens here and closes at finish; "queued"
                # runs until admission
                self.trace.thread(rid + 1, f"req {rid}")
                self.trace.begin(rid + 1, "request",
                                 args={"prompt_len": req.prompt_len,
                                       "max_tokens": max_tokens})
                self.trace.begin(rid + 1, "queued")
        return RequestHandle(req, self)

    @property
    def has_work(self) -> bool:
        return any(r is not None for r in self.active) or len(self.sched) > 0

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self.active)

    # ------------------------------------------------------------ admission
    def _admit(self) -> int:
        """Admit queued requests into free slots; returns the count placed.

        Contiguous: reset slot caches first — recurrent SSM/RG-LRU states
        integrate garbage while a slot idles; KV entries are position-
        masked but cleared too. Paged: reserve the request's worst-case
        pages and publish its block-table row instead; admission is
        additionally gated on the free-page budget via `fits`. Admission
        is token-budget-aware: active slots never exceed the per-tick
        budget, so every slot advances every tick.

        Called at tick START and AGAIN after slots free at tick end, so an
        early-terminating (stop-token) request's capacity becomes an
        admission the same tick it finishes.

        PREEMPTION POLICY (paged + `EngineConfig.preempt`): after normal
        admission, while the queue head STRICTLY outranks the lowest-
        priority active request and remains blocked, that victim (ties:
        latest admitted) is preempted — spilled host-side and requeued —
        and admission re-runs. Strictness means a requeued request can
        never evict its own priority class, so there is no ping-pong.
        """
        with self._queue_lock:
            return self._admit_locked()

    def _admit_locked(self) -> int:
        paged = self.cache_cfg.paged
        fits = None
        if paged:
            ps = self.cache_cfg.page_size

            # cache-aware admission: the longest resident prefix of the
            # request's page hashes is SHARED (pinned, read-only) and
            # only the uncached page count charges the free budget.
            # Allocation happens right here, inside the check — admit's
            # contract (fits(head) True => head is admitted) makes the
            # mutation safe, and it keeps the budget exact when one
            # tick both pins cached pages and evicts cold ones.
            def fits(r):
                need = self.alloc.pages_needed(r.kv_need)
                if r.spill is not None:
                    # resume: the kept shared prefix is still pinned, so
                    # only the extension charges the budget; the spilled
                    # content is restored right after placement
                    if not self.alloc.can_resume(r.rid, need):
                        return False
                    r.pages = r.pages + self.alloc.resume(r.rid, need)
                    return True
                # always re-feed at least the last prompt token (its
                # logits produce the first generated token), so the
                # matchable prefix stops one position short of the end
                hashes = r.page_hashes[
                    : (r.n_prefix + r.prompt_len - 1) // ps]
                if not self.alloc.can_alloc(need, hashes):
                    return False
                r.pages, shared = self.alloc.alloc(r.rid, need, hashes)
                r.cached_len = shared * ps
                r.published = shared
                return True

        def admit_now():
            free = [s for s, r in enumerate(self.active) if r is None]
            room = self.token_budget - self.active_count
            return self.sched.admit(free, self.tick, fits=fits,
                                    max_admit=max(0, room))

        n = self._place(admit_now())
        if self.preempt_enabled:
            while True:
                head = self.sched.head
                if head is None:
                    break
                victims = [(r.priority, -r.admit_tick, s)
                           for s, r in enumerate(self.active)
                           if r is not None]
                if not victims:
                    break
                pri, _, victim_slot = min(victims)
                if head.priority <= pri:
                    break      # strict: equals never evict each other
                self.preempt(victim_slot)
                n += self._place(admit_now())
        return n

    def _place(self, placed) -> int:
        """Per-request placement bookkeeping for `sched.admit` results:
        block-table row / slot reset, trace span flip, sampling-row fill,
        and — for resumed requests — the spilled-state restore."""
        paged = self.cache_cfg.paged
        if paged and self.alloc.pending_restores:
            # host-tier prefix hits: admission matched hashes whose pages
            # were evicted to host memory; scatter their packed content
            # back into the fresh pages before any of them is read
            pr, self.alloc.pending_restores = self.alloc.pending_restores, []
            ids = [p for p, _ in pr]
            host = jax.tree.map(
                lambda *ls: np.concatenate(ls, axis=ls[0].ndim - 4),
                *[c for _, c in pr])
            self.cache = restore_pages(self.cache, ids, host)
            if self.mesh.shape["model"] > 1 \
                    and self._cache_sharding is not None:
                self.cache = jax.device_put(self.cache, self._cache_sharding)
            self._m_restore_pages.inc(len(ids))
        for slot, req in placed:
            resumed = req.spill is not None
            if paged:
                self.block_tables[slot] = self.alloc.block_table_row(
                    req.rid, self.block_tables.shape[1])
                if not resumed:
                    self._m_cached.inc(req.cached_len)
            else:
                self.cache = self._reset(self.cache, slot)
            if not resumed:
                self._m_prompt.inc(req.n_prefix + req.prompt_len)
                req.admit_ns = now_ns()
            if self.trace.enabled:
                self.trace.end(req.rid + 1,
                               "preempted" if resumed else "queued",
                               args={"slot": slot,
                                     "cached_len": req.cached_len})
                self.trace.begin(req.rid + 1,
                                 "decode" if (resumed and req.tokens)
                                 else "prefill")
            self.active[slot] = req
            # prefill skip: cached pages already hold positions
            # [0, cached_len), so this slot starts feeding there
            self.fed[slot] = req.cached_len
            fill_slot(self.samp, slot, req.sampling, req.key_data,
                      req.max_tokens)
            req.status = PREFILL
            if resumed:
                self._restore_slot(slot, req)
        return len(placed)

    def _restore_slot(self, slot: int, req: Request) -> None:
        """Scatter a resumed request's spilled page content into its fresh
        pages and rewind slot state to the exact spilled position. The
        restored planes are byte-identical (packed AMS round trip) and the
        sampling key folds only (rid, token index) with ``ngen`` restored
        below, so the continued stream is bit-identical to one that was
        never preempted — and nothing is ever re-prefilled."""
        sp = req.spill
        if sp.n_pages:
            new_pages = req.pages[sp.n_keep:sp.n_keep + sp.n_pages]
            self.cache = restore_pages(self.cache, new_pages, sp.content)
            if self.mesh.shape["model"] > 1 \
                    and self._cache_sharding is not None:
                # outside-jit scatters can drop the head-sharded layout;
                # re-place so the next dispatch sees its expected sharding
                self.cache = jax.device_put(self.cache, self._cache_sharding)
            self._m_restore_pages.inc(sp.n_pages)
        self.fed[slot] = sp.fed
        self.last_token[slot] = sp.last_token
        self.samp["ngen"][slot] = req.n_generated
        # re-publish restored prompt pages from the kept-prefix boundary:
        # publish() is a no-op wherever the original page is still resident
        req.published = sp.n_keep
        req.status = DECODE if req.tokens else PREFILL
        req.spill = None
        self.resumes += 1
        self._m_resume.inc()

    def preempt(self, slot: int) -> Request:
        """Preempt the request in `slot`: snapshot its private pages'
        content host-side (packed planes — `cache.pool.extract_pages`),
        release those pages (shared prefix stays pinned), clear the slot,
        and requeue the request ahead of its priority class. Public so
        tests can force preemption at arbitrary stream positions; the
        engine's own policy calls this from `_admit`."""
        req = self.active[slot]
        if req is None:
            raise ValueError(f"slot {slot} is idle")
        if not self.cache_cfg.paged:
            raise RuntimeError("preemption requires a paged cache")
        ps = self.cache_cfg.page_size
        fed = int(self.fed[slot])
        n_keep = req.cached_len // ps            # shared prefix: pinned
        n_touched = -(-fed // ps)                # pages holding content
        spill_ids = req.pages[n_keep:n_touched]
        content = extract_pages(self.cache, spill_ids) if spill_ids else None
        nbytes = host_bytes(content) if spill_ids else 0
        # snapshot BEFORE release: a released page may be reused by the
        # very next alloc
        self.alloc.preempt(req.rid, n_keep)
        req.pages = req.pages[:n_keep]
        req.spill = SpilledState(
            fed=fed, last_token=int(self.last_token[slot]), content=content,
            n_pages=len(spill_ids), n_keep=n_keep, nbytes=nbytes)
        req.preemptions += 1
        req.status = PREEMPTED
        req.slot = -1
        self.active[slot] = None
        clear_slot(self.samp, slot)
        self.block_tables[slot] = 0
        self.fed[slot] = 0
        self.last_token[slot] = 0
        self.preemptions += 1
        self.spill_pages += len(spill_ids)
        self.spill_bytes += nbytes
        self._m_preempt.inc()
        self._m_spill_pages.inc(len(spill_ids))
        self._m_spill_bytes.inc(nbytes)
        if self.trace.enabled:
            self.trace.end(req.rid + 1,
                           "decode" if req.tokens else "prefill")
            self.trace.begin(req.rid + 1, "preempted",
                             args={"spill_pages": len(spill_ids),
                                   "fed": fed})
        with self._queue_lock:
            self.sched.requeue(req)
        return req

    # ----------------------------------------------------------------- tick
    def step(self) -> Dict[str, object]:
        """One engine tick: admit, run the slot-masked ragged step, advance
        slots by their consumed chunk lengths.

        Returns {"finished": [Request], "generated": int, "active": int}.
        Exactly ``step_end(step_begin())`` — the split form is the
        double-buffering seam async drivers use (host free between the two
        halves while the device computes).
        """
        return self.step_end(self.step_begin())

    def step_begin(self) -> _PendingStep:
        """First half of a tick: admission (+ preemption policy), chunk
        sizing, ragged input build, and the ASYNC dispatch of the jitted
        step. Returns the in-flight handle `step_end` consumes; raises if a
        step is already in flight. Between `step_begin` and `step_end` the
        host thread is free — the async frontend parks the engine thread
        there so its event loop serves HTTP/SSE/submissions under the
        device compute of tick t (work for tick t+1 lands in the queue
        before t's `step_end` runs its same-tick re-admit)."""
        if self._pending is not None:
            raise RuntimeError("step already in flight (step_end not called)")
        t0 = time.perf_counter()
        ph = self.phases
        ph.begin_tick()                  # engine.admit
        paged = self.cache_cfg.paged
        C = self.step_chunk              # token-buffer width fed to the step
        PC = self.chunk                  # prefill growth cap per slot
        tracing = self.trace.enabled
        with jax.set_mesh(self.mesh):
            # 1) admit queued requests into free slots (see _admit)
            self._admit()

            if self.active_count == 0:
                # idle ticks still advance the engine clock — open-loop
                # drivers gate future arrivals on eng.tick
                self._close_tick({"idle": True})
                self.tick += 1
                self._m_idle.inc()
                with self._tick_cv:
                    self._tick_cv.notify_all()
                return _PendingStep(
                    outs=None, nvalid=None, ndraft=None, t0=t0, fed=0,
                    tracing=tracing, idle=True,
                    result={"finished": [], "generated": 0, "active": 0})
            self._m_active.set(self.active_count)

            ph.mark("plan")
            # 2) size each slot's chunk under the global token budget:
            #    every active slot gets 1 guaranteed token; prefilling slots
            #    grow toward the prefill chunk (never past the prompt end),
            #    pure-decode slots append up to speculate_k DRAFT tokens —
            #    both only from the leftover budget
            nvalid = np.zeros(self.slots, np.int32)
            ndraft = np.zeros(self.slots, np.int32)
            proposals: Dict[int, np.ndarray] = {}
            leftover = self.token_budget - self.active_count
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                n = 1
                rem = req.n_prefix + req.prompt_len - int(self.fed[s])
                if PC > 1 and rem > 1:     # still prefilling past this tick
                    extra = min(min(PC, rem) - 1, leftover)
                    n += max(0, extra)
                    leftover -= n - 1
                elif self.speculate_k and rem <= 0:
                    # decode round: drafts past the length cap could write
                    # beyond the slot's reserved kv_need positions, so the
                    # cap also bounds the draft count
                    k_cap = min(self.speculate_k,
                                req.max_tokens - 1 - req.n_generated,
                                leftover)
                    if k_cap > 0:
                        hist = np.concatenate(
                            [req.prompt, np.asarray(req.tokens, np.int32)])
                        d = np.asarray(self.drafter.propose(hist, int(k_cap)),
                                       np.int32).reshape(-1)[:k_cap]
                        if d.size:
                            self.drafter.record_proposal(int(d.size))
                            proposals[s] = d
                            ndraft[s] = d.size
                            n += int(d.size)
                            leftover -= int(d.size)
                nvalid[s] = n

            ph.mark("build")
            # 3) build this tick's ragged inputs: [B, C] token block per
            #    slot, per-slot start position + valid length
            token = np.zeros((self.slots, C), np.int32)
            pos = np.full(self.slots, -1, np.int32)    # idle: write-suppressed
            use_prefix = self.cfg.num_prefix_embeds > 0
            embeds = (np.zeros((self.slots, C, self.cfg.d_model), np.float32)
                      if use_prefix else None)
            emask = np.zeros((self.slots, C), bool) if use_prefix else None
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                i = int(self.fed[s])
                # shared (read-only) pages cover exactly [0, cached_len):
                # this tick's inserts start at i, so they only ever land in
                # the request's private pages
                assert i >= req.cached_len, (
                    f"slot {s}: insert at {i} would write a shared page "
                    f"(cached prefix {req.cached_len})")
                if req.first_step_tick < 0:
                    req.first_step_tick = self.tick
                pos[s] = i
                for j in range(int(nvalid[s])):
                    idx = i + j
                    if idx < req.n_prefix:
                        embeds[s, j] = req.prefix_embeds[idx]
                        emask[s, j] = True
                    elif idx < req.n_prefix + req.prompt_len:
                        token[s, j] = req.prompt[idx - req.n_prefix]
                    elif j == 0 or s not in proposals:
                        token[s, j] = self.last_token[s]
                    else:                  # chunk tail: this round's drafts
                        token[s, j] = proposals[s][j - 1]

            # 4) ONE jitted step for every slot (ragged when C > 1); the
            #    per-slot sampling rows ride along as one pytree arg and
            #    the step hands back the sampled token + in-step done flag
            if C > 1:
                args = (self.params, jnp.asarray(token), jnp.asarray(pos),
                        jnp.asarray(nvalid))
                if self.speculate_k:
                    args += (jnp.asarray(ndraft),)
                args += (self.cache,)
            else:
                args = (self.params, jnp.asarray(token[:, 0]),
                        jnp.asarray(pos), self.cache)
            if paged:
                args += (jnp.asarray(self.block_tables),)
            if use_prefix:
                if C > 1:
                    args += (jnp.asarray(embeds), jnp.asarray(emask))
                else:
                    args += (jnp.asarray(embeds[:, 0]),
                             jnp.asarray(emask[:, 0]))
            args += ({k: jnp.asarray(v) for k, v in self.samp.items()},)
            fed = int(nvalid.sum())
            self._m_steps.inc()
            self._m_fed.inc(fed)
            if self._paged_attn_layers:
                self._count_paged_attn_pages(pos, nvalid)
            for s in range(self.slots):
                if self.active[s] is not None:
                    self._m_chunk.observe(int(nvalid[s]))
            ph.mark("dispatch")
            outs = self._step(*args)     # async dispatch: the device is
            #                              now computing; nothing below in
            #                              step_end blocks until np.asarray
        p = _PendingStep(outs=outs, nvalid=nvalid, ndraft=ndraft,
                         t0=t0, fed=fed, tracing=tracing)
        self._pending = p
        ph.mark(None)
        return p

    def _count_paged_attn_pages(self, pos: np.ndarray,
                                nvalid: np.ndarray) -> None:
        """Count this tick's paged-attention grid cells by the kernel's own
        rule: a slot attends ceil(longest query length / page) pages of its
        row (its last valid query sees pos + nvalid keys; an idle slot, pos
        < 0, none) in every layer, and skips the rest of the row."""
        page = self.cache_cfg.page_size
        width = self.block_tables.shape[1]
        top = np.where(pos >= 0, pos + nvalid, 0)
        npg = np.minimum(-(-top // page), width)
        attended = int(npg.sum()) * self._paged_attn_layers
        self._m_pages_att.inc(attended)
        self._m_pages_skip.inc(
            self.slots * width * self._paged_attn_layers - attended)

    def step_end(self, pending: Optional[_PendingStep] = None) -> Dict[str, object]:
        """Second half of a tick: block on the in-flight device step,
        advance slot state by consumed chunk lengths, publish pages,
        finish / roll back speculation / same-tick re-admit. Accepts the
        handle from `step_begin` (or uses the stored one)."""
        p = self._pending if pending is None else pending
        if p is None:
            raise RuntimeError("no step in flight (call step_begin first)")
        self._pending = None
        if p.idle:
            return p.result
        t0, tracing, fed = p.t0, p.tracing, p.fed
        nvalid, ndraft, outs = p.nvalid, p.ndraft, p.outs
        paged = self.cache_cfg.paged
        ph = self.phases
        ph.mark("wait")
        with jax.set_mesh(self.mesh):
            # the first np.asarray blocks until the step's outputs are on
            # the host: the end of engine.wait
            if self.speculate_k:
                out_tok, n_emit, acc, done, self.cache = outs
                out_tok = np.asarray(out_tok)
                n_emit = np.asarray(n_emit)
                acc = np.asarray(acc)
            else:
                next_tok, done, self.cache = outs
                next_tok = np.asarray(next_tok)
            done = np.asarray(done)
            t_host = ph.mark("advance")

            # 5) advance slot state by consumed chunk lengths; collect
            #    sampled tokens; free finished
            finished, generated = [], 0
            tick_reads = 0                   # roofline attribution (obs.cost)
            tick_ach_bytes = 0.0
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                i = int(self.fed[s])
                n = int(nvalid[s])
                self.fed[s] = i + n
                if self.cost_model is not None:
                    # causal floor: fed token j attends positions [0, i+j]
                    # plus its own insert; achieved: what the configured
                    # cache impl actually moves (StepCostModel branches —
                    # dense capacity for contiguous, full block-table row
                    # + f32 dequant round-trip for the paged ref gather,
                    # causal whole pages and NO dequant for the fused
                    # template, which restores packed planes in VREGs)
                    reads = n * i + n * (n + 1) // 2
                    cm = self.cost_model
                    ach_bytes = cm.achieved_kv_bytes(
                        i, n, cache_kind=self.cache_cfg.kind,
                        impl=self.cache_cfg.impl, capacity=self.capacity,
                        page_size=self.cache_cfg.page_size,
                        max_pages=self.cache_cfg.max_pages_per_seq,
                        bytes_per_token=self._kv_bpt)
                    req.kv_floor_bytes += \
                        (n + reads) * cm.kv_bytes_per_token
                    req.kv_achieved_bytes += ach_bytes
                    tick_reads += reads
                    tick_ach_bytes += ach_bytes
                if paged and req.page_hashes:
                    # publish full PROMPT pages as prefill crosses their
                    # boundaries: content-addressed, so an identical prefix
                    # admitted later references the same physical page.
                    # Pages holding generated tokens are never published.
                    filled = min(int(self.fed[s]), req.prompt_len)
                    while (req.published + 1) * self.cache_cfg.page_size <= filled:
                        j = req.published
                        self.alloc.publish(req.rid, req.page_hashes[j],
                                           req.pages[j])
                        req.published = j + 1
                if i + n - 1 >= req.n_prefix + req.prompt_len - 1:
                    # this chunk consumed the last prompt token or a generated
                    # token -> the last valid position's draw is the next
                    # generated token (speculative rounds emit the accepted
                    # draft prefix + the bonus/corrective draw in one go)
                    k_s = int(ndraft[s])
                    if self.speculate_k:
                        a = int(acc[s])
                        emitted = [int(t) for t in out_tok[s, :int(n_emit[s])]]
                        if k_s:
                            self._m_spec_prop.inc(k_s)
                            self._m_spec_acc.inc(a)
                            req.drafted += k_s
                            req.accepted_drafts += a
                    else:
                        emitted = [int(next_tok[s])]
                    was_first = not req.tokens
                    req.tokens.extend(emitted)
                    tok = emitted[-1]
                    self.last_token[s] = tok
                    self.samp["ngen"][s] = len(req.tokens)
                    generated += len(emitted)
                    self._m_emit.inc()
                    if was_first:
                        req.first_token_tick = self.tick
                        req.first_token_ns = t_host
                        req.status = DECODE
                        if tracing:
                            self.trace.end(req.rid + 1, "prefill")
                            self.trace.begin(req.rid + 1, "decode")
                    if bool(done[s]):
                        # in-step termination: stop-token hit or length cap
                        req.finish_tick = self.tick
                        req.finish_ns = t_host
                        req.status = FINISHED
                        req.finish_reason = (
                            "stop" if tok in req.sampling.stop_token_ids
                            else "length")
                        self.finished.append(req)
                        finished.append(req)
                        self.active[s] = None
                        clear_slot(self.samp, s)
                        if paged:
                            self.alloc.free(req.rid)
                            self.block_tables[s] = 0
                        (self._m_fin_stop if req.finish_reason == "stop"
                         else self._m_fin_len).inc()
                        self._m_ttft.observe(req.ttft_ticks)
                        self._m_lat.observe(req.latency_ticks)
                        self._m_glen.observe(req.n_generated)
                        self._m_ttft_s.observe(
                            (req.first_token_ns - req.submit_ns) * 1e-9)
                        self._m_wait_s.observe(
                            (req.admit_ns - req.submit_ns) * 1e-9)
                        if tracing:
                            self.trace.end(req.rid + 1, "decode")
                            self.trace.instant(
                                req.rid + 1, "finished",
                                args={"reason": req.finish_reason,
                                      "tokens": req.n_generated})
                            self.trace.end(req.rid + 1, "request")
                    elif k_s:
                        # ROLLBACK: the step already zero-scattered the
                        # rejected draft entries (positions i+1+a .. i+k_s)
                        # out of every cache leaf; rewind the feed position
                        # so the next round re-inserts there. Speculation
                        # starts strictly after the prompt, so the rewind
                        # target can never reach a shared prefix page.
                        new_fed = i + 1 + a
                        assert new_fed >= req.n_prefix + req.prompt_len \
                            and new_fed > req.cached_len - 1, (
                            f"slot {s}: speculative rewind to {new_fed} "
                            f"would cross the shared/prompt boundary "
                            f"(cached {req.cached_len}, prompt end "
                            f"{req.n_prefix + req.prompt_len})")
                        self.fed[s] = new_fed
            if self.cost_model is not None:
                cm = self.cost_model
                self._m_floor_b.inc(cm.tick_floor_bytes(fed, tick_reads))
                self._m_floor_f.inc(cm.tick_floor_flops(fed, tick_reads))
                self._m_kv_floor.inc(
                    (fed + tick_reads) * cm.kv_bytes_per_token)
                self._m_kv_ach.inc(tick_ach_bytes)
            # freed capacity becomes admission headroom the SAME tick: a
            # stop-token hit admits the queue head before the tick closes
            # (its first chunk runs next tick)
            ph.mark("readmit")
            if finished:
                self._admit()
        self._close_tick({"generated": generated})
        self.tick += 1
        self._m_tick_s.observe(time.perf_counter() - t0)
        self._m_tick_tok.observe(generated)
        if tracing:
            self.trace.counter("engine", {"active": self.active_count,
                                          "queue": self.sched.queue_depth})
        with self._tick_cv:
            self._tick_cv.notify_all()
        return {"finished": finished, "generated": generated,
                "active": self.active_count}

    def wait_tick(self, tick: int, timeout: float = 0.5) -> None:
        """Block until the engine clock passes `tick` (RequestHandle
        waiters use this when an external driver owns `step()`); the
        timeout bounds the wait in case that driver stops mid-flight."""
        with self._tick_cv:
            self._tick_cv.wait_for(
                lambda: self.tick > tick or not self.driver_active,
                timeout=timeout)

    # ------------------------------------------------------------------ run
    def run(self, max_ticks: int = 1_000_000) -> Dict[str, float]:
        """Drive up to `max_ticks` further ticks, stopping early once queue +
        slots drain. Returns aggregate stats; per-request results live on
        the Request objects."""
        for _ in range(max_ticks):
            if not self.has_work:
                break
            self.step()
        return self.stats()

    def reset_metrics(self) -> None:
        """Drop accumulated timing/counter state (e.g. after a jit warmup)
        without touching in-flight requests or the cache. Registry
        registrations (and callback gauges) survive — only values zero."""
        self.finished = []
        self.preemptions = self.resumes = 0
        self.spill_pages = self.spill_bytes = 0
        self.metrics.reset()
        if self.alloc is not None:
            self.alloc.reset_stats()

    # --------------------------------------------------------- obs plumbing
    @property
    def _emit_rounds(self) -> int:
        """Slot-rounds that emitted tokens (registry-backed; the counter
        behind stats()['tokens_per_step'])."""
        return int(self._m_emit.value)

    def _close_tick(self, args: Dict[str, object]) -> None:
        """File this tick's phases (`TickPhases.close_tick`) and, when
        tracing, export them nested in the tick's span."""
        phases = self.phases.close_tick(self.tick)
        self.trace.nested(0, "tick", phases, args={"tick": self.tick, **args})

    # ----------------------------------------------------------- accounting
    def kv_bytes_per_token(self) -> int:
        """PER-DEVICE cache bytes one token occupies across all layers, in
        the active cache mode (bf16 slot/page storage, or AMS packed
        planes). On a head-sharded tp>1 mesh each device holds kv/tp heads
        of every page, so this scales as 1/tp — the residency/bandwidth
        number the paper's wins are about. tp=1: the full-pool bytes,
        unchanged."""
        from repro.cache.pool import pool_bytes_per_token
        dims = model_dims(self.cfg, self.mesh.shape["model"])
        return self.cfg.num_layers * pool_bytes_per_token(
            dims.kv // self._kv_shards, dims.hd, self.cache_cfg)

    def kv_compression_vs_bf16(self) -> float:
        """bf16-cache bytes / active-mode bytes per token (1.0 for bf16)."""
        dims = model_dims(self.cfg, self.mesh.shape["model"])
        return compression_vs_bf16(dims.kv, dims.hd, self.cache_cfg)

    def stats(self) -> Dict[str, float]:
        """Aggregate serving stats, computed FROM the metrics registry
        (`repro.obs.metrics`) — the tick histograms keep raw observations
        in insertion order, so every percentile below is bit-identical to
        the pre-registry hand-counter implementation (pinned by
        tests/test_obs.py). With ``ObsConfig(enabled=False)`` the
        accumulated telemetry reads as zero; pure-state values (kv bytes
        per token, queue depth) stay real."""
        raw_s = self._m_tick_s.raw_values()
        raw_t = self._m_tick_tok.raw_values()
        tick_s = np.asarray(raw_s) if raw_s else np.zeros(1)
        tok = np.asarray(raw_t) if raw_t else np.zeros(1)
        total_s = float(tick_s.sum())
        decode_ticks = tick_s[tok > 0]
        # TTFT (submit -> first token) and end-to-end request latency, in
        # engine ticks over finished requests — TTFT is the number chunked
        # prefill moves (ceil(prompt/C) prefill ticks instead of prompt_len)
        # requests end at VARIABLE lengths (stop tokens): both arrays are
        # per-request actuals, so early exits shorten the percentiles
        ttft = np.asarray(self._m_ttft.raw_values(), np.float64)
        e2e = np.asarray(self._m_lat.raw_values(), np.float64)
        glen = np.asarray(self._m_glen.raw_values(), np.float64)
        spec_prop = int(self._m_spec_prop.value)
        spec_acc = int(self._m_spec_acc.value)
        emit_rounds = int(self._m_emit.value)

        def pct(a, q):
            return float(np.percentile(a, q)) if a.size else 0.0

        out = {
            "ticks": len(raw_s),
            "requests_finished": int(self._m_finished.total),
            "tokens_generated": int(tok.sum()),
            "tokens_per_s": float(tok.sum() / total_s) if total_s else 0.0,
            "decode_ms_median": (1e3 * float(np.median(decode_ticks))
                                 if decode_ticks.size else 0.0),
            "decode_ms_p99": (1e3 * float(np.percentile(decode_ticks, 99))
                              if decode_ticks.size else 0.0),
            "ttft_ticks_mean": float(ttft.mean()) if ttft.size else 0.0,
            "ttft_ticks_p50": pct(ttft, 50),
            "ttft_ticks_p99": pct(ttft, 99),
            "latency_ticks_mean": float(e2e.mean()) if e2e.size else 0.0,
            "latency_ticks_p50": pct(e2e, 50),
            "latency_ticks_p99": pct(e2e, 99),
            "gen_tokens_mean": float(glen.mean()) if glen.size else 0.0,
            "stopped_early": int(self._m_fin_stop.value),
            "queue_depth": self.sched.queue_depth,
            "kv_bytes_per_token": self.kv_bytes_per_token(),
            "kv_compression_vs_bf16": self.kv_compression_vs_bf16(),
            # speculative decoding: drafts scored / accepted, and tokens
            # emitted per emitting slot-round (1.0 when not speculating —
            # every emission is a single draw)
            "spec_proposed": spec_prop,
            "spec_accepted": spec_acc,
            "accept_rate": spec_acc / spec_prop if spec_prop else 0.0,
            "tokens_per_step": (float(tok.sum()) / emit_rounds
                                if emit_rounds else 0.0),
            # preemption / host-spill tier (plain ints: real even with
            # ObsConfig(enabled=False), like the allocator's hit counters)
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "spill_pages": self.spill_pages,
            "spill_bytes": self.spill_bytes,
        }
        if self.alloc is not None:
            out["free_pages"] = self.alloc.free_pages
            out.update(self.alloc.stats())
            prompt_toks = self._m_prompt.value
            out["cached_token_frac"] = (
                self._m_cached.value / prompt_toks if prompt_toks else 0.0)
        if self.cost_model is not None:
            # roofline attribution (obs.cost; full report: obs.attribution)
            cm = self.cost_model
            measured = float(out["kv_bytes_per_token"])
            kv_floor = self._m_kv_floor.value
            kv_ach = self._m_kv_ach.value
            out["kv_bytes_per_token_floor"] = cm.kv_bytes_per_token
            out["kv_bytes_per_token_ideal"] = cm.kv_ideal_bytes_per_token
            out["kv_floor_ratio"] = measured / cm.kv_bytes_per_token
            out["kv_vs_ideal_floor"] = measured / cm.kv_ideal_bytes_per_token
            out["kv_achieved_vs_floor"] = (kv_ach / kv_floor
                                           if kv_floor else 0.0)
            out["floor_hbm_bytes"] = self._m_floor_b.value
            out["floor_flops"] = self._m_floor_f.value
        return out
