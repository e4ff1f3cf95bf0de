"""Serving driver: offline AMS-Quant PTQ -> continuous-batching decode.

The paper's deployment scenario: weights are quantized/packed ahead of time
(§3.3 "Ahead-of-time weight packing"), then the decode loop streams packed
planes and restores on the fly. Serving runs on the continuous-batching
engine in ``repro.launch.engine`` (``ServeEngine``): requests enter a FIFO
queue, a scheduler admits them into free KV-cache slots, and one jitted
slot-masked decode step serves all in-flight requests per tick.

``generate`` below is a thin fixed-batch wrapper over that engine, kept for
one-shot use and benchmarks (quantized vs fp16 generations agree to high
token-match rate — see tests/test_engine.py and tests/test_serve_quant.py).
On CPU this runs reduced configs end to end; on a pod the same step builder
carries the production mesh shardings.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --scheme fp5.33-e2m3 --tokens 32

``--no-reduced`` serves the published widths (a chip's worth of memory).

For true streaming-arrival serving, construct ``ServeEngine`` directly (see
examples/serve_continuous.py and benchmarks/bench_serving.py).
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.config import EngineConfig
from repro.launch.engine import ServeEngine


def generate(arch: str, *, reduced=True, scheme="fp5.33-e2m3",
             strategy="set_lsb", impl="ref", mesh_kind="none",
             batch=2, prompt_len=16, gen_tokens=16, seed=0,
             params=None, capacity=None, prompts=None, prefix_embeds=None,
             sampling=None):
    """One-shot batched generation via the continuous-batching engine.

    Submits ``batch`` requests at tick 0 (prompts drawn from ``seed`` unless
    given explicitly as ``prompts`` [batch, prompt_len]) and drains the
    engine. Returns (tokens [batch, gen_tokens], stats).

    ``sampling`` (a `repro.launch.sampling.SamplingParams`, or one per
    request) turns on per-request stochastic decoding; stop tokens can then
    end streams early, so the token array is padded with -1 past each
    stream's end. Default is greedy, bit-identical to earlier PRs.
    """
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()

    rng = np.random.default_rng(seed)
    if prompts is None:
        prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len))
    prompts = np.asarray(prompts, np.int32)
    batch, prompt_len = prompts.shape  # explicit prompts win over the kwargs
    cap = capacity or (prompt_len + gen_tokens + cfg.num_prefix_embeds)
    if cfg.num_prefix_embeds and prefix_embeds is None:
        prefix_embeds = rng.standard_normal(
            (batch, cfg.num_prefix_embeds, cfg.d_model)).astype(np.float32)

    eng = ServeEngine(
        EngineConfig(arch=arch, reduced=reduced, scheme=scheme,
                     strategy=strategy, impl=impl, mesh_kind=mesh_kind,
                     slots=batch, capacity=cap, seed=seed, verbose=True),
        params=params)
    per_req = (sampling if isinstance(sampling, (list, tuple))
               else [sampling] * prompts.shape[0])
    reqs = [eng.submit(prompts[b], gen_tokens,
                       prefix_embeds=(prefix_embeds[b]
                                      if prefix_embeds is not None else None),
                       sampling=per_req[b])
            for b in range(prompts.shape[0])]
    stats = eng.run()
    # stop tokens make streams ragged; pad the tail with -1 (never a token)
    width = max(r.n_generated for r in reqs)
    toks = np.full((len(reqs), width), -1, np.int32)
    for b, r in enumerate(reqs):
        toks[b, :r.n_generated] = r.tokens
    return toks, stats


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced() toy widths (default); --no-reduced "
                         "serves the published widths")
    ap.add_argument("--scheme", default="fp5.33-e2m3")
    ap.add_argument("--strategy", default="set_lsb")
    ap.add_argument("--impl", default="ref")
    ap.add_argument("--mesh", default="none")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (default); >0 samples on-device")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    sampling = None
    if args.temperature > 0:
        from repro.launch.sampling import SamplingParams
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.sample_seed)
    toks, stats = generate(args.arch, reduced=args.reduced,
                           scheme=args.scheme, strategy=args.strategy,
                           impl=args.impl, mesh_kind=args.mesh,
                           batch=args.batch, prompt_len=args.prompt,
                           gen_tokens=args.tokens, sampling=sampling)
    print("generated tokens:\n", toks)
    print("stats:", stats)


if __name__ == "__main__":
    main()
