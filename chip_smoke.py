#!/usr/bin/env python3
"""Chip smoke: serve qwen2-7b at its published widths on a TPU.

Run from the repo root, in one process:

    python chip_smoke.py             # one chip: both lowering tiers
    python chip_smoke.py --chips 4   # four chips: tp=4 streams vs tp=1

One chip: builds qwen2-7b (``reduced=False``, random weights from seed 0)
once, quantized to fp5.33-e2m3 one layer group at a time, and serves it
through ``EngineConfig -> ServeEngine`` with a paged_ams KV cache
(fp4.25-e2m2 pages) and chunked prefill, in both tiers the engine offers:
the XLA tier (``impl="ref"``) and the Pallas tier (``impl="pallas"`` with
``CacheConfig(impl="pallas")``). Each tier serves 4 requests of about 512
prompt tokens and 32 new tokens. On the chip it also checks the Pallas
``ams_matmul`` against ``ref.dequant_full`` at the model's linear shapes
and the paged Pallas attention against ``paged_attention_ref`` on the
engine's own pool.

Four chips (``--chips 4``): the same packed tree served tensor-parallel
over a (1, 4) mesh (one kv head per chip) must give token streams
bit-identical to tp=1, request for request. No other phase runs.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or when the repo's ``src/`` is not beside it. The last line of a
passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2-7b"
SCHEME = "fp5.33-e2m3"       # weights; the paged_ams pages keep fp4.25-e2m2
SLOTS, PROMPT, NEW = 4, 512, 32
CHUNK, PAGE = 16, 16          # prefill chunk per slot per tick; page tokens
SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found platform {devs[0].platform!r} "
             f"({len(devs)} {devs[0].device_kind} device(s))")
    if len(devs) < n:
        fail(f"need {n} TPU chips, JAX found {len(devs)}")
    return devs


class CompileClock:
    """Sums XLA backend-compile seconds as JAX reports them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def build_packed_params(cfg):
    """qwen2-7b at published widths, fp5.33-e2m3 packed, one layer group
    at a time (`init_serving_params`): the bf16 tree never exists."""
    import jax
    from repro.core.policy import QuantPolicy
    from repro.models import init_serving_params
    quant = QuantPolicy(scheme=SCHEME, min_elements=1 << 10)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_serving_params(jax.random.PRNGKey(SEED), cfg, quant=quant))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"[build] {ARCH} reduced=False d_model={cfg.d_model} "
        f"layers={cfg.num_layers} vocab={cfg.vocab_size}: {SCHEME} packed "
        f"tree {nbytes / 2**30:.3f} GiB in {time.perf_counter() - t0:.1f} s")
    return params


def prompts(cfg):
    import numpy as np
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT - 32, PROMPT + 1, SLOTS)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]


def engine_config(*, impl: str, cache_impl: str, mesh=None):
    from repro.cache import CacheConfig
    from repro.launch.config import EngineConfig
    return EngineConfig(
        arch=ARCH, reduced=False, scheme=SCHEME, impl=impl, seed=SEED,
        slots=SLOTS, capacity=PROMPT + NEW, prefill_chunk=CHUNK,
        cache=CacheConfig(kind="paged_ams", page_size=PAGE, impl=cache_impl),
        mesh=mesh)


def serve(name, eng, cfg, clock, dev, on_prefilled=None):
    """Serve the fixed workload; check every request finished in-vocab.
    ``on_prefilled(eng)`` runs once, on the first tick after which every
    slot holds a prefilled request. Returns the streams in request order."""
    c0, t0 = clock.seconds, time.perf_counter()
    for p in prompts(cfg):
        eng.submit(p, max_tokens=NEW)
    ticks = 0
    while eng.has_work:
        eng.step()
        ticks += 1
        if on_prefilled is not None and all(
                r is not None and r.tokens for r in eng.active):
            on_prefilled(eng)
            on_prefilled = None
    wall = time.perf_counter() - t0
    reqs = sorted(eng.finished, key=lambda r: r.rid)
    if len(reqs) != SLOTS:
        fail(f"{name}: {len(reqs)} of {SLOTS} requests finished")
    for r in reqs:
        if r.finish_reason != "length" or len(r.tokens) != NEW:
            fail(f"{name}: request {r.rid} ended {r.finish_reason!r} after "
                 f"{len(r.tokens)} of {NEW} tokens")
        bad = [t for t in r.tokens if not 0 <= t < cfg.vocab_size]
        if bad:
            fail(f"{name}: request {r.rid} emitted out-of-vocabulary ids {bad}")
    if on_prefilled is not None:
        fail(f"{name}: the slots never all held prefilled requests")
    log(f"[{name}] {SLOTS} requests x (~{PROMPT} prompt + {NEW} new) tokens: "
        f"all finished in {ticks} ticks, {wall:.1f} s wall "
        f"(compile {clock.seconds - c0:.1f} s); peak HBM {peak_gib(dev)}")
    streams = [list(map(int, r.tokens)) for r in reqs]
    for r, s in zip(reqs, streams):
        log(f"[{name}] request {r.rid} (prompt {r.prompt_len}): {s}")
    return streams


def first_divergence(a, b):
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)


def check_matmul(params, cfg, *, interpret: bool = False):
    """Pallas ams_matmul vs `ref.dequant_full` at the model's linear shapes.

    * one-hot rows: y = e_k @ W must equal row k of dequant_full EXACTLY —
      both compute one lattice code times its f32 channel scale, rounded
      once, so any mis-decoded or misplaced weight shows;
    * random bf16 rows: |y - x @ W| <= 2^-14 * (|x| @ |W|) elementwise —
      the kernel multiplies exact bf16 products and accumulates in f32
      (rounding 2^-24 per add over K <= 18944 terms, well under 2^-14 of
      the absolute sum), while a wrong tile or scale moves whole terms."""
    import jax
    import jax.numpy as jnp
    from repro.core.formats import get_scheme
    from repro.core.packing import PackedWeight, make_layout
    from repro.kernels import ops, ref
    lay = make_layout(get_scheme(SCHEME))
    layer0 = jax.tree.map(lambda a: a[0], params["layers"]["sub0"])
    D, F = cfg.d_model, cfg.d_ff
    linears = [("attn", "wq", D), ("attn", "wk", D), ("attn", "wo", D),
               ("ffn", "w_gate", D), ("ffn", "w_down", F)]
    key = jax.random.PRNGKey(1)
    worst = 0.0
    for blk, name, K in linears:
        p = layer0[blk][name]
        pw = PackedWeight(p["hi"], p["lsb"], p["scale"], lay, K,
                          p["scale"].shape[-1])
        w = ref.dequant_full(pw, jnp.float32)
        ks = jax.random.randint(key, (8,), 0, K)
        onehot = jax.nn.one_hot(ks, K, dtype=jnp.bfloat16)
        y = ops.ams_matmul(onehot, pw, interpret=interpret)
        if not bool(jnp.array_equal(y, w[ks])):
            n = int(jnp.sum(y != w[ks]))
            fail(f"ams_matmul {name} [{K}x{pw.N}]: one-hot rows differ from "
                 f"dequant_full in {n} entries")
        for B in (1, SLOTS * CHUNK):
            key, sub = jax.random.split(key)
            x = jax.random.normal(sub, (B, K), jnp.bfloat16)
            y = ops.ams_matmul(x, pw, interpret=interpret)
            xf = x.astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                want = xf @ w
                bound = jnp.abs(xf) @ jnp.abs(w)
            rel = float(jnp.max(jnp.abs(y - want) / bound))
            worst = max(worst, rel)
            if not rel <= 2.0 ** -14:
                fail(f"ams_matmul {name} [{B}x{K}]x[{K}x{pw.N}]: error "
                     f"{rel:.3g} of |x|@|W| exceeds 2^-14")
    log(f"[kernels] ams_matmul {SCHEME}: one-hot rows exact; random rows "
        f"max error {worst:.3g} of |x|@|W| (bound 2^-14 = {2**-14:.3g}) "
        f"at {len(linears)} linear shapes x B in (1, {SLOTS * CHUNK})")


def check_attention(eng, cfg, *, interpret: bool = False):
    """Paged Pallas attention vs `paged_attention_ref` on the engine's own
    layer-0 pool, block tables and lengths (chunk 1 and chunk CHUNK).

    Bound: |out - ref| <= 2^-6 * max|ref|. Both read the same packed pages
    and restore the same lattice values; the reference runs its f32 dots
    at highest precision while the kernel's f32 MXU dots may round each
    operand to bf16 (2^-8 relative), which moves softmax weights by about
    2^-8 times the score range — a misread page moves whole rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.cache import paged_attention_ref
    from repro.kernels.attention_template import fused_paged_attention
    from repro.models import model_dims
    from repro.models.attention import kv_index_map
    dims = model_dims(cfg)
    ccfg = eng.cache_cfg
    pool = jax.tree.map(lambda a: a[0], eng.cache["layers"]["sub0"])
    bt = jnp.asarray(eng.block_tables)
    fed = np.asarray(eng.fed, np.int32)
    kvm = kv_index_map(dims.H, dims.H_true, dims.kv)
    worst = 0.0
    for c in (1, CHUNK):
        key = jax.random.PRNGKey(2 + c)
        if c == 1:
            q = jax.random.normal(key, (SLOTS, dims.H, dims.hd), jnp.bfloat16)
            lengths = jnp.asarray(fed)
        else:
            q = jax.random.normal(key, (SLOTS, c, dims.H, dims.hd),
                                  jnp.bfloat16)
            lengths = jnp.asarray(np.maximum(
                fed[:, None] - c + 1 + np.arange(c)[None, :], 0), jnp.int32)
        got = fused_paged_attention(q, pool, lengths, bt, page_size=PAGE,
                                    kv_scheme=ccfg.kv_scheme,
                                    interpret=interpret)
        with jax.default_matmul_precision("highest"):
            want = paged_attention_ref(q, pool, lengths, bt, ccfg, kv_map=kvm)
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        worst = max(worst, rel)
        if not rel <= 2.0 ** -6:
            fail(f"paged attention chunk {c}: error {rel:.3g} of max|ref| "
                 f"exceeds 2^-6")
    log(f"[kernels] paged Pallas attention ({ccfg.kv_scheme} pages, "
        f"{int(fed.max())} keys max) vs paged_attention_ref: max error "
        f"{worst:.3g} of max|ref| (bound 2^-6) at chunk 1 and {CHUNK}")


def one_chip(dev):
    import jax
    from repro.configs import get_config
    from repro.launch.engine import ServeEngine
    cfg = get_config(ARCH)
    clock = CompileClock()
    log(f"[device] {dev.platform} {dev.device_kind}, "
        f"{len(jax.devices())} device(s) visible, using 1")
    log(f"[note] the fp16/bf16 weight scheme is not run: qwen2-7b's bf16 "
        f"tree is 15.2 GB and does not fit one 16 GB chip beside a cache")
    params = build_packed_params(cfg)
    log(f"[build] peak HBM {peak_gib(dev)}")
    check_matmul(params, cfg)

    eng = ServeEngine(engine_config(impl="ref", cache_impl="ref"),
                      params=params)
    xla = serve("xla tier", eng, cfg, clock, dev)
    del eng
    gc.collect()

    eng = ServeEngine(engine_config(impl="pallas", cache_impl="pallas"),
                      params=params)
    pallas = serve("pallas tier", eng, cfg, clock, dev,
                   on_prefilled=lambda e: check_attention(e, cfg))
    for i, (a, b) in enumerate(zip(xla, pallas)):
        d = first_divergence(a, b)
        log(f"[streams] request {i}: xla vs pallas first divergence at "
            f"token {'none' if d is None else d} (not gated: argmax near-ties "
            f"on random weights)")
    log(f"[compile] {clock.seconds:.1f} s of XLA compilation in total")


def four_chips(devs):
    import jax
    from repro.configs import get_config
    from repro.launch.engine import ServeEngine
    from repro.launch.mesh import make_driver_mesh, make_serving_mesh
    cfg = get_config(ARCH)
    clock = CompileClock()
    log(f"[device] {devs[0].platform} {devs[0].device_kind}, "
        f"{len(devs)} devices; tp=4 (one of qwen2-7b's 4 kv heads per chip)")
    params = build_packed_params(cfg)
    streams = {}
    for tp, mesh in ((1, make_driver_mesh("none")), (4, make_serving_mesh(4))):
        eng = ServeEngine(engine_config(impl="ref", cache_impl="ref",
                                        mesh=mesh), params=params)
        streams[tp] = serve(f"tp={tp}", eng, cfg, clock, devs[0])
        del eng
        gc.collect()
    for i, (a, b) in enumerate(zip(streams[1], streams[4])):
        d = first_divergence(a, b)
        if d is not None:
            fail(f"request {i}: tp=4 stream leaves tp=1 at token {d}: "
                 f"{a[d]} vs {b[d]}")
    log(f"[streams] tp=4 streams bit-identical to tp=1 for all {SLOTS} "
        f"requests")
    log(f"[compile] {clock.seconds:.1f} s of XLA compilation in total")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel tp=4 vs tp=1 phase")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the repo's src/repro is not beside {os.path.basename(__file__)}")
    sys.path.insert(0, src)
    devs = tpu_devices(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[cache] compilation cache at {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(devs[:4])
    else:
        one_chip(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
