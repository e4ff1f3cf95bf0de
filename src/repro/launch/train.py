"""End-to-end training driver: data -> microbatched pjit step -> checkpoints,
with fault containment, straggler monitoring, and elastic DP re-sharding.

On this container it runs REAL small-scale training (CPU, 1 device) — the
quickstart trains a ~10M model to visibly decreasing loss; on a pod the same
driver runs the production mesh (mesh_kind=single/multi).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --steps 50 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.data import DataConfig, SyntheticLM, prefix_embeds_stub
from repro.launch.fault_tolerance import (
    FailureInjector,
    RunGuard,
    StragglerMonitor,
    heartbeat_file,
)
from repro.launch.mesh import make_production_mesh, make_driver_mesh
from repro.launch.steps import build_train_step
from repro.models import init_params
from repro.optim import init_state


def make_mesh(kind: str):
    return make_driver_mesh(kind)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--mesh", default="none", choices=["none", "single", "multi"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--dp-size", type=int, default=1,
                    help="data shards for the (elastic) host pipeline")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ag"])
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rcfg = RunConfig(model=cfg, seq_len=args.seq_len,
                     global_batch=args.global_batch, mode="train",
                     microbatch=args.microbatch, learning_rate=args.lr,
                     warmup_steps=max(5, args.steps // 10),
                     grad_compression=args.grad_compression)
    mesh = make_mesh(args.mesh)

    with jax.set_mesh(mesh):
        step_fn, shapes, shards = build_train_step(mesh, cfg, rcfg)
        params = init_params(jax.random.PRNGKey(0), cfg,
                             tp=mesh.shape["model"])
        params = jax.device_put(params, shards["params"])
        opt_state = jax.device_put(init_state(params), shards["opt_state"])

        prefix_n = cfg.num_prefix_embeds
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=args.seq_len - prefix_n,
                                      global_batch=args.global_batch))

        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if mgr is not None:
            restored, rstep = mgr.restore({"params": params, "opt": opt_state})
            if restored is not None:
                params = jax.device_put(restored["params"], shards["params"])
                opt_state = jax.device_put(restored["opt"], shards["opt_state"])
                start = rstep
                print(f"[restore] resumed from step {start}", flush=True)

        def restore_fn() -> int:
            nonlocal params, opt_state
            if mgr is None:
                return 0
            mgr.wait()
            restored, rstep = mgr.restore({"params": params, "opt": opt_state})
            if restored is None:
                return 0
            params = jax.device_put(restored["params"], shards["params"])
            opt_state = jax.device_put(restored["opt"], shards["opt_state"])
            return rstep

        injector = FailureInjector()
        monitor = StragglerMonitor()
        guard = RunGuard(restore_fn)
        losses = []

        step = start
        while step < args.steps:
            t0 = time.time()
            captured = {}

            def one_step(step=step):
                nonlocal params, opt_state
                injector.maybe_fail(step)
                toks, tgts = data.batch(step, shard=0,
                                        num_shards=1)  # host feed; device
                # sharding comes from in_shardings
                pre = prefix_embeds_stub(cfg, args.global_batch, seed=step)
                if pre is None:
                    pre = np.zeros((args.global_batch, 0, cfg.d_model),
                                   np.float32)
                params, opt_state, metrics = step_fn(
                    params, opt_state, jnp.asarray(toks), jnp.asarray(tgts),
                    jnp.asarray(pre), jnp.int32(step))
                captured.update(jax.tree.map(float, metrics))

            nxt = guard.run(step, one_step)
            if nxt <= step:  # restored backwards
                step = nxt
                continue
            dt = time.time() - t0
            monitor.observe(step, dt)
            losses.append(captured.get("loss", float("nan")))
            if step % args.log_every == 0:
                print(f"step {step:5d}  loss {captured.get('loss', -1):.4f}  "
                      f"gnorm {captured.get('grad_norm', -1):.3f}  "
                      f"lr {captured.get('lr', -1):.2e}  {dt:.2f}s", flush=True)
            if mgr is not None and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
            if args.ckpt_dir:
                heartbeat_file(f"{args.ckpt_dir}/heartbeat", step)
            step = nxt

        if mgr is not None:
            mgr.save(args.steps, {"params": params, "opt": opt_state},
                     blocking=True)
            mgr.wait()
        if monitor.straggles:
            print(f"[straggler] slow steps: {monitor.straggles}")
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
              f"median step {monitor.median:.2f}s")
        return losses


if __name__ == "__main__":
    main()
