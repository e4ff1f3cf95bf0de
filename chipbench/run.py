#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark and print one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (``BENCHMARK.json``) names a
configuration (``chipbench/configs/<name>.json``) and a traffic mix
(``chipbench/traffic/<name>.json``); per-layer metrics are read by
``chipbench/metrics/<name>.py``; the comparison limit of a cell is in
``chipbench/cells/<cell>.json``. Adding any of them needs no edit here, and
neither does a configuration of another block (q/k/v biases or none, RoPE
with linear position scaling or without) or a cell on more chips: the
configuration file states the block, and the cell's ``chips`` is the
tensor-parallel degree the weights are made, placed and served at.

Set-up (``setup_s``, from process start to the window) makes the weights on
the chip from the seed, builds the serving engine, and runs the closed
loop's first requests until each has its first token: that compiles or
loads the cell's one step shape and opens the window on a full decode batch.
The window then runs the closed loop for ``--seconds``; with ``--trace 1`` a
slice of it is profiled and the per-layer metrics are printed instead of the
end-to-end ones. After the window, once the engine's state is freed, a
seeded sample of the requests served so far, the longest among them, is
compared token by token with the plain reference (`reference.py`):
``correct`` is that comparison.

``--rehearse`` runs the same flow on the CPU at the configuration's and the
mix's rehearsal sizes, with the Pallas kernels in interpret mode; it is for
checking the flow here, and no measured run takes it.

The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for (at rehearsal size: fewer CPU devices), or
when the program is not in ``src/``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def fail(msg: str, code: int = 1):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_limits(cell: str, rehearse: bool) -> dict:
    """The cell's comparison limits (``cells/<cell>.json``), at rehearsal
    size its ``rehearsal`` ones."""
    with open(os.path.join(HERE, "cells", f"{cell}.json")) as f:
        c = json.load(f)
    return (c["rehearsal"] if rehearse else c)["limits"]


def cell_metrics(spec: dict, kind: str, cell: str):
    return [mt for mt in spec[kind]
            if "workloads" not in mt or cell in mt["workloads"]]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    sp = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def start_jax(rehearse: bool):
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # a fixed path inside the checkout: the path is part of the cache key
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    if rehearse:      # CPU programs stay out of the chip's cache directory
        jax.config.update("jax_enable_compilation_cache", False)
        return jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1


@dataclasses.dataclass
class Run:
    """What a per-layer reader may read."""

    model: object
    traffic: dict
    record: object        # driver.Record
    trace: object         # devtrace.Trace or None
    peak: dict
    slots: int

    def window_ticks(self):
        return [t for t in self.record.ticks if t.end <= self.record.seconds]

    def traced_work(self):
        """`work.tick_work` summed over the traced ticks: one chip's share."""
        import work
        ticks = [t for t in self.record.ticks if t.traced]
        return work.sum_work(self.model, ticks, self.peak) if ticks else {}


def end_to_end(record, setup_s: float):
    """The window's end-to-end numbers (host clock, tracing off), over the
    ticks that ended inside it: a rate over whole ticks, and every
    inter-token gap that lies inside them."""
    import numpy as np
    ends = [t.end for t in record.ticks if t.end <= record.seconds]
    out = {"setup_s": setup_s}
    if not ends:
        return out
    last = ends[-1]
    tokens, gaps = 0, []
    for f in record.flights:
        tt = [t for t in f.token_times if 0.0 <= t <= last]
        tokens += len(tt)
        gaps += [b - a for a, b in zip(tt, tt[1:])]
    out["output_tok_s"] = tokens / last
    if gaps:
        out["tpot_ms"] = 1e3 * float(np.sum(gaps)) / len(gaps)
        out["itl_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
    return out


def pick_sample(flights, n: int, seed: int):
    """The requests compared with the reference: the longest (prompt plus
    tokens served so far) of those with a served token, and n - 1 more
    drawn from the seed."""
    import numpy as np
    cand = [f for f in flights if f.first is not None]
    if not cand:
        return []
    longest = max(cand, key=lambda f: len(f.req.prompt) + len(f.token_times))
    rest = [f for f in cand if f is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served(flight, vocab: int):
    """A request's tokens served so far, and whether they are sound: every
    id in the vocabulary, and a finished request has all it asked for."""
    toks = list(map(int, flight.handle.request.tokens))
    ok = (all(0 <= t < vocab for t in toks)
          and (not flight.done or len(toks) == flight.req.max_tokens))
    return toks, ok


def serve(m, tr, seed: int, trace_dir=None):
    """Set-up as a run makes it: the weights from the seed, the engine, and
    the closed loop's first requests stepped to their first tokens."""
    import system
    import traffic
    from driver import Driver
    cfg = system.program_config(m)
    eng = system.make_engine(m, tr, system.build_params(m, cfg, seed))
    drv = Driver(eng, traffic.Mix(tr, seed, m.vocab), tr["clients"],
                 trace_dir=trace_dir)
    drv.fill()
    eng.reset_metrics()
    return eng, drv


def judge(gap: float, sound: bool, limits: dict):
    """``correct`` and the numbers compared, each beside its limit."""
    import math
    limit = limits["logit_gap_mean"]
    checks = {"logit_gap_mean": {"value": gap if math.isfinite(gap) else None,
                                 "limit": limit},
              "sampled_tokens_sound": {"value": int(sound), "limit": 1}}
    return bool(sound and gap <= limit), checks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, Pallas interpret mode")
    ap.add_argument("--save-trace", default=None,
                    help="also write the reduced trace to this .json path")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"the program is not at {os.path.join(ROOT, 'src', 'repro')}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    spec = load_spec()
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        fail(f"no workload {args.workload!r} in BENCHMARK.json", 2)
    cell = cells[args.workload]
    jax = start_jax(args.rehearse)
    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < cell["chips"]:
        fail(f"{args.workload} needs {cell['chips']} chips, JAX found "
             f"{len(devs)}")
    devs = devs[:cell["chips"]]

    import numpy as np
    import devtrace
    import reference
    import traffic
    import work
    from model import load_model

    m = load_model(cell["config"], rehearse=args.rehearse,
                   chips=cell["chips"])
    tr = traffic.load_traffic(cell["traffic"])
    if args.rehearse:
        tr = traffic.rehearsal(tr)
    limits = load_limits(args.workload, args.rehearse)
    kind = devs[0].device_kind
    peak = {} if args.rehearse else work.peaks(kind)
    compiles = CompileCounter(jax)

    # ------------------------------------------------------------- set-up
    trace_dir = (tempfile.mkdtemp(prefix="chipbench-trace-")
                 if args.trace else None)
    eng, drv = serve(m, tr, args.seed, trace_dir)
    # what set-up built lives to the end: keep it out of the cyclic
    # collector, whose full passes over it otherwise stall a tick now and
    # then (0.4-2.3 s in 1 run of 3 on a v5e host, PERF.md)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.1f} s ({compiles.n} programs compiled or loaded; "
        f"{drv.fill_ticks} ticks in {drv.fill_s:.1f} s gave the "
        f"{tr['clients']} clients their first tokens)")

    # ------------------------------------------------------------- window
    before = compiles.n
    record = drv.run(args.seconds)
    in_window_compiles = compiles.n - before
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {}
    tr_red = None
    if args.trace:
        tr_red = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.save_trace:
            with open(args.save_trace, "w") as f:
                json.dump(tr_red.to_json(), f)
        w = devtrace.window(tr_red)
        busy = devtrace.busy_seconds(tr_red)
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = (w[1] - w[0]) if w else 0.0
        run = Run(m, tr, record, tr_red, peak, tr["slots"])
        for mt in cell_metrics(spec, "per_layer", args.workload):
            v = load_reader(mt["name"])(run)
            if v is not None:
                result[mt["name"]] = {"value": v, "unit": mt["unit"]}
        breakdown = {"device_ops": devtrace.top_ops(tr_red),
                     "idle_gaps": devtrace.idle_gaps(tr_red)}
    else:
        e2e = end_to_end(record, setup_s)
        for mt in cell_metrics(spec, "end_to_end", args.workload):
            if mt["name"] in e2e:
                result[mt["name"]] = {"value": e2e[mt["name"]],
                                      "unit": mt["unit"]}

    # --------------------------------------------------- correctness
    attempted = len(record.flights)
    failed = sum(1 for f in record.flights if not served(f, m.vocab)[1])
    sample = pick_sample(record.flights, tr["sample"], args.seed)
    prompts = [np.asarray(f.req.prompt) for f in sample]
    outputs, sound = [], bool(sample)
    for f in sample:
        toks, ok = served(f, m.vocab)
        outputs.append(toks)
        sound = sound and ok
    window_ticks = [t for t in record.ticks if t.end <= record.seconds]
    dur = np.asarray([t.end - t.begin for t in window_ticks])
    if dur.size:
        slow = np.flatnonzero(dur > 1.5 * np.median(dur))
        log(f"window ticks: {dur.size}, median {1e3 * np.median(dur):.1f} ms, "
            f"longest {1e3 * dur.max():.1f} ms; {slow.size} over 1.5x the "
            f"median (at ticks {slow[:8].tolist()})")
    # free the program's state before the reference runs
    eng.params = eng.cache = None
    del eng, drv, record, sample
    gc.collect()
    t_ref = time.perf_counter()
    if sound:
        gap, widest = reference.gap_check(m, args.seed, prompts, outputs,
                                          tr["capacity"])
    else:
        gap = widest = float("inf")
    correct, checks = judge(gap, sound, limits)
    log(f"reference {time.perf_counter() - t_ref:.1f} s; widest logit gap "
        f"{widest} (not compared: see PERF.md)")
    log(f"window {args.seconds:.0f} s: {attempted} requests, {failed} "
        f"unsound, {len(window_ticks)} whole ticks; {in_window_compiles} "
        f"programs compiled in the window")
    log(f"sample: {len(prompts)} requests, {sum(map(len, outputs))} served "
        f"tokens; peak HBM {mem / 2**30:.3f} GiB")
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr,
              flush=True)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": result, "device": device}
    if args.trace:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
