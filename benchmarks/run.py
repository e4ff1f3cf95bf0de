"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (and tees a copy into
experiments/bench_results.txt):

    Table 2 / Fig.3 / Fig.5  -> bench_formats_accuracy (CE + weight-MSE proxy)
    §3.1 Adaptive Searching  -> bench_adaptive_search
    Table 3 / Fig.6          -> bench_kernel_speedup (analytic Table-3 model
                                + CPU wall-clock plumbing check; the
                                ``kernel_attn/`` rows compare fused-template
                                vs ref achieved KV bytes per cache scheme
                                and hard-assert the fused path never
                                materializes dequantized pages in HBM)
    Serving (beyond-paper)   -> bench_serving (fp16 vs AMS engine throughput
                                under one Poisson workload: contiguous,
                                paged, chunked-prefill, shared-prefix
                                (prefix-cache hit rate / cached-token
                                fraction), sampled (per-request
                                temperature/top-p + stop tokens) and
                                speculative (k-draft verify; accept_rate /
                                tokens_per_step columns) rows in the
                                same CSV)
    §Roofline summary        -> bench_roofline (reads experiments/dryrun)

Run: PYTHONPATH=src python -m benchmarks.run [--quick]

ARTIFACTS (uploaded by the CI bench job with ``if: always()``):
    experiments/bench_results.txt       — every CSV row of the sweep
    experiments/serving_trace-*.json    — Perfetto-loadable chrome trace of
                                          the shared-prefix + speculative
                                          serving row, per scheme (load at
                                          ui.perfetto.dev; see
                                          docs/observability.md)
    experiments/serving_trace-*.prom    — Prometheus text-format snapshot of
                                          the same run's metrics registry
The paged serving row additionally re-runs itself with observability
disabled and asserts 0% perturbation of the deterministic tick/stream
metrics (``--obs-check``), so telemetry can never silently invalidate the
committed baseline.

REGRESSION GATE (``--check benchmarks/baseline.csv``): after the sweep,
the serving rows are compared against a committed baseline and the run
exits non-zero on a >15% regression in any deterministic serving metric —
engine ticks to drain the fixed workload (the decode-tick throughput
measure), TTFT / latency tick percentiles, or kv-bytes-per-token. These
are exact given ``--seed``, so ANY drift is a real behaviour change, not
runner noise. Wall-clock-derived numbers (tokens/s, ms percentiles, the
``x=`` speedup ratio) are NOT gated — they do not transfer across
machines, and the --quick workload is too small to time reliably even as
a ratio. A decode-throughput regression still trips the gate as extra
engine ticks on the fixed workload. Regenerate the baseline after an
intentional change with ``--write-baseline benchmarks/baseline.csv``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def bench_roofline(out_lines):
    """Summarize dry-run roofline terms if dry-run records exist."""
    try:
        from repro.analysis.roofline import analyze, load_records
    except Exception as e:  # pragma: no cover
        print(f"roofline/skip,0,import_error={e!r}")
        return
    recs = load_records("experiments/dryrun", "pod256")
    if not recs:
        line = "roofline/skip,0,no dry-run records (run repro.launch.dryrun)"
        print(line)
        out_lines.append(line)
        return
    for r in recs:
        a = analyze(r)
        line = (f"roofline/{r['arch']}/{r['shape']},0,"
                f"dom={a['dominant']} compute_s={a['compute_s']:.4g} "
                f"memory_s={a['memory_s']:.4g} "
                f"collective_s={a['collective_s']:.4g} "
                f"useful={a['useful_flops_ratio']} "
                f"roofline_frac={a['roofline_fraction']}")
        print(line, flush=True)
        out_lines.append(line)


# --------------------------------------------------------------------------
# bench regression gate
# --------------------------------------------------------------------------
# deterministic serving metrics (exact given the workload seed): any move
# past the tolerance is a real scheduling/termination/layout change
GATED = {
    "ticks": ("higher", 0.15),
    "ttft_ticks_p50": ("higher", 0.15),
    "ttft_ticks_p99": ("higher", 0.15),
    "latency_ticks_p50": ("higher", 0.15),
    "latency_ticks_p99": ("higher", 0.15),
    "kv_bytes_per_token": ("higher", 0.15),
    # speculative decoding: both are exact given the seed (deterministic
    # drafters, greedy verify); fewer accepted drafts or fewer tokens per
    # emitting round is a real speculation regression
    "accept_rate": ("lower", 0.15),
    "tokens_per_step": ("lower", 0.15),
    # preemption (serving/overload row): scheduling decisions are exact
    # given the seed — more preemptions is scheduler thrash, and a LOWER
    # count here means the priority policy stopped firing (the row's
    # in-run assert additionally pins preemptive p99 TTFT < head-of-line)
    "preemptions": ("higher", 0.15),
    "resumes": ("higher", 0.15),
    # kernel_attn rows (fused template vs ref, StepCostModel accounting —
    # exact analytic bytes): more achieved bytes per causal-floor byte is a
    # lowering regression, and ANY dequant_kb on a fused row means packed
    # pages got re-materialized in HBM (baseline pins it at 0)
    "kv_vs_floor": ("higher", 0.15),
    "dequant_kb": ("higher", 0.15),
    # NOT gated: anything wall-clock-derived. Even the AMS/fp16 speedup
    # ratio x (machine speed divides out) swings >2x between modes of one
    # --quick run on CPU — the workload is far too small to time reliably.
    # Decode-throughput regressions still show: a slower schedule = more
    # engine ticks to drain the same fixed workload.
}


GATED_PREFIXES = ("serving/", "kernel_attn/")


def parse_rows(lines):
    """'name,us_per_call,k=v k=v ...' -> {name: {k: float}} (gated rows:
    serving + the fused-attention accounting rows)."""
    rows = {}
    for ln in lines:
        ln = ln.strip()
        if not ln or ln.startswith("#") \
                or not ln.startswith(GATED_PREFIXES):
            continue
        name, _, rest = ln.split(",", 2)
        fields = {}
        for part in rest.split():
            key, sep, val = part.partition("=")
            if sep:
                try:
                    fields[key] = float(val)
                except ValueError:
                    pass
        rows[name] = fields
    return rows


def check_regression(out_lines, baseline_path) -> int:
    """Compare this run's serving rows against the committed baseline.
    Returns the number of regressions (printed); missing rows count IN
    BOTH DIRECTIONS — a baseline row this run no longer produces, and a
    row this run registered that the baseline has never seen (previously
    a new row silently escaped the gate until someone remembered to
    regenerate the baseline)."""
    with open(baseline_path) as f:
        base = parse_rows(f)
    cur = parse_rows(out_lines)
    failures = []
    for name in sorted(set(cur) - set(base)):
        failures.append(
            f"{name}: row not in baseline — regenerate with "
            f"--write-baseline {baseline_path}")
    for name, bfields in sorted(base.items()):
        if name not in cur:
            failures.append(f"{name}: row missing from this run")
            continue
        for metric, (direction, tol) in GATED.items():
            if metric not in bfields:
                continue
            b, c = bfields[metric], cur[name].get(metric)
            if c is None:
                failures.append(f"{name}: metric {metric} disappeared")
                continue
            if direction == "higher":
                bad = c > b * (1 + tol) + 1e-9
            else:
                bad = c < b * (1 - tol) - 1e-9
            if bad:
                failures.append(
                    f"{name}: {metric} {b:g} -> {c:g} "
                    f"({'+' if c > b else ''}{100 * (c - b) / b if b else 0:.0f}%, "
                    f"tol {tol:.0%} {direction}-is-worse)")
    for f_ in failures:
        print(f"REGRESSION {f_}", flush=True)
    if not failures:
        print(f"# regression gate: {len(base)} baseline rows OK "
              f"(vs {baseline_path})")
    return len(failures)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer training steps for the accuracy bench")
    ap.add_argument("--skip-accuracy", action="store_true")
    ap.add_argument("--check", metavar="BASELINE",
                    help="compare serving rows against a committed baseline "
                         "CSV; exit non-zero on >tolerance regressions")
    ap.add_argument("--write-baseline", metavar="PATH",
                    help="write this run's serving rows as the new baseline")
    args = ap.parse_args()

    # the serving sweep's tensor-parallel row (--mesh tp2) needs 2 devices;
    # force them on the host platform BEFORE anything imports jax — this is
    # metric-neutral for every other row (tick/latency/kv columns are
    # deterministic and single-device rows never touch device 1)
    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=2")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    out_lines = []
    t0 = time.time()

    from benchmarks import bench_adaptive_search, bench_kernel_speedup

    print("# === adaptive search ablation (paper §3.1) ===", flush=True)
    bench_adaptive_search.run(out_lines)

    print("# === kernel speedup (paper Table 3) ===", flush=True)
    bench_kernel_speedup.run(out_lines)

    print("# === fused attention template: achieved KV bytes vs ref ===",
          flush=True)
    bench_kernel_speedup.run_attention(out_lines)

    print("# === serving: contiguous vs paged vs chunked vs shared-prefix "
          "vs speculative ===", flush=True)
    from benchmarks import bench_serving
    bench_serving.run(out_lines, quick=args.quick)

    if not args.skip_accuracy:
        print("# === format accuracy sweep (paper Table 2 / Fig.3/5) ===",
              flush=True)
        from benchmarks import bench_formats_accuracy
        bench_formats_accuracy.run(out_lines,
                                   steps=80 if args.quick else 250)

    print("# === roofline summary (§Roofline) ===", flush=True)
    bench_roofline(out_lines)

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.txt", "w") as f:
        f.write("\n".join(out_lines) + "\n")
    print(f"# done in {time.time()-t0:.0f}s "
          f"({len(out_lines)} rows -> experiments/bench_results.txt)")

    if args.write_baseline:
        serving = [ln for ln in out_lines
                   if ln.startswith(GATED_PREFIXES)]
        with open(args.write_baseline, "w") as f:
            f.write("# bench regression baseline — serving + kernel_attn "
                    "rows of a --quick sweep.\n# Gated metrics (see "
                    "benchmarks/run.py GATED): ticks, ttft/latency tick\n"
                    "# percentiles, kv_bytes_per_token, kv_vs_floor, "
                    "dequant_kb — deterministic\n# given the seed; 15% "
                    "tolerance (dequant_kb=0 rows pin exactly).\n"
                    "# Regenerate: python -m benchmarks.run --quick "
                    "--write-baseline benchmarks/baseline.csv\n")
            f.write("\n".join(serving) + "\n")
        print(f"# wrote {len(serving)} serving rows -> {args.write_baseline}")

    if args.check:
        n_bad = check_regression(out_lines, args.check)
        if n_bad:
            sys.exit(f"{n_bad} bench regression(s) vs {args.check}")


if __name__ == "__main__":
    main()
