#!/usr/bin/env python3
"""The control readings that set a cell's limit, made once on the chip; no
benchmark run takes them.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 3 --seconds 4

For each seed, in one process: the run's own set-up (`run.serve`), a short
window of the cell's closed loop and the run's seeded sample; then, on the
sample's prompts and served tokens, the reference's gaps of the served
tokens (what a run compares) and of the tokens the control ranks first at
the same positions. The control is the reference computed one precision
step below the configuration (``control_weights``, ``control_kv``). Both
go through the run's own verdict (`run.judge`) at the cell's limit: the
program has to come out correct and the control not.

``--rehearse`` runs it on the CPU at rehearsal sizes.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import run as harness  # noqa: E402


def readings(m, tr, limits, seed: int, seconds: float) -> dict:
    """One seed's program and control readings, each with its verdict."""
    import numpy as np
    import reference
    t0 = time.perf_counter()
    eng, drv = harness.serve(m, tr, seed)
    rec = drv.run(seconds)
    sample = harness.pick_sample(rec.flights, tr["sample"], seed)
    prompts = [np.asarray(f.req.prompt) for f in sample]
    outputs = [harness.served(f, m.vocab)[0] for f in sample]
    eng.params = eng.cache = None
    del eng, drv, rec, sample
    gc.collect()
    r = reference.control_gaps(m, seed, prompts, outputs, tr["capacity"])
    r["program_correct"] = harness.judge(r["served_mean"], True, limits)[0]
    r["control_correct"] = harness.judge(r["control_mean"], True, limits)[0]
    return dict(seed=seed, **r, seconds=time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    jax = harness.start_jax(args.rehearse)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        harness.fail("no TPU")
    spec = harness.load_spec()
    cell = {c["name"]: c for c in spec["workloads"]}[args.workload]
    import traffic
    from model import load_model
    m = load_model(cell["config"], rehearse=args.rehearse,
                   chips=cell["chips"])
    tr = traffic.load_traffic(cell["traffic"])
    if args.rehearse:
        tr = traffic.rehearsal(tr)
    limits = harness.load_limits(args.workload, args.rehearse)
    rows = []
    for seed in args.seeds:
        rows.append(readings(m, tr, limits, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload, "control": [m.control_weights, m.control_kv],
        "limit": limits["logit_gap_mean"],
        "served_mean_max": max(r["served_mean"] for r in rows),
        "control_mean_min": min(r["control_mean"] for r in rows),
        "served_widest_max": max(r["served_widest"] for r in rows),
        "control_widest_min": min(r["control_widest"] for r in rows),
        "program_correct_all": all(r["program_correct"] for r in rows),
        "control_correct_any": any(r["control_correct"] for r in rows)}),
        flush=True)


if __name__ == "__main__":
    main()
