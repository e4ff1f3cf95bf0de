#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py <cell> [<cell> ...]

For each cell: the engine step at the cell's slots, chunk, capacity and
page pool; the weight maker's layer program; and the reference's layer
program at the cell's padded length. The step and the maker compile on a
(1, chips) mesh of the cell's chips, placed as a run places them; the
reference on one chip. Prints each program's ``memory_analysis``
(argument, output and temporary bytes, on each chip), so that a cell whose
pool does not fit a chip is found here and not on the chip. A compile that
passes is not a chip run.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import reference  # noqa: E402
import system  # noqa: E402
import traffic  # noqa: E402
from model import load_model, seed_key  # noqa: E402

GiB = 2 ** 30


def describe(name, compiled):
    ma = compiled.memory_analysis()
    arg, out, tmp = (ma.argument_size_in_bytes, ma.output_size_in_bytes,
                     ma.temp_size_in_bytes)
    print(f"  {name}: arguments {arg / GiB:.3f} GiB, outputs "
          f"{out / GiB:.3f} GiB, temporaries {tmp / GiB:.3f} GiB", flush=True)
    return arg, out, tmp


def main(cells):
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by = {c["name"]: c for c in spec["workloads"]}
    from repro.cache import CacheConfig
    from repro.configs.base import RunConfig
    from repro.launch.steps import build_engine_step
    for name in cells:
        cell = by[name]
        m = load_model(cell["config"], chips=cell["chips"])
        tr = traffic.load_traffic(cell["traffic"])
        cfg = system.program_config(m)
        print(f"{name}: {m.chips} chip(s)", flush=True)
        mesh = Mesh(np.array(topo.devices[:m.chips]).reshape(1, m.chips),
                    ("data", "model"))
        rep = NamedSharding(mesh, P())
        ccfg = CacheConfig(kind="paged_ams", page_size=m.page_size,
                           num_pages=tr.get("num_pages", 0), kv_scheme=m.kv,
                           kv_strategy=m.strategy, impl=m.cache_impl
                           ).sized(capacity=tr["capacity"], slots=tr["slots"])
        rcfg = RunConfig(model=cfg, seq_len=tr["capacity"],
                         global_batch=tr["slots"], mode="decode",
                         quant=system._policy(m))
        step, shapes, placed = build_engine_step(
            mesh, cfg, rcfg, cache_cfg=ccfg, chunk=m.prefill_chunk,
            sampling=True)
        with jax.set_mesh(mesh):
            args = [jax.tree.map(
                lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
                tree, placed.get(n, jax.tree.map(lambda _: rep, tree)))
                for n, tree in shapes.items()]
            a, o, t = describe(
                f"engine step [{tr['slots']} x {m.prefill_chunk}], "
                f"{ccfg.num_pages} pages x {ccfg.max_pages_per_seq} per slot",
                step.lower(*args).compile())
        print(f"  engine step total {(a + t) / GiB:.3f} GiB of 16 GB "
              f"({100 * (a + t) / 16e9:.1f} %) a chip", flush=True)

        k0 = seed_key(0)
        ref_key = jax.ShapeDtypeStruct(k0.shape, k0.dtype, sharding=one)
        key = jax.ShapeDtypeStruct(k0.shape, k0.dtype, sharding=rep)
        layers, outer = system.maker(m, mesh if m.chips > 1 else None)
        describe("weight maker, packed layers", layers.lower(key).compile())
        describe("weight maker, embedding and head",
                 outer.lower(key).compile())
        S, T = tr["sample"], -(-tr["capacity"] // 512) * 512
        x = jax.ShapeDtypeStruct((S, T, m.d_model), jnp.float32, sharding=one)
        layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        describe(f"reference layer [{S} x {T}]",
                 reference._layer.lower(ref_key, layer, x, m=m,
                                        wfmt=m.weights, kvfmt=m.kv,
                                        qblock=512).compile())


if __name__ == "__main__":
    main(sys.argv[1:])
