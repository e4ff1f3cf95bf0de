"""The weight maker: the one-chip tree is pinned, and on a tensor-parallel
mesh it makes the same tree, placed as the program's step places it, which
the engine serves to the same tokens."""

import hashlib
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

import system
from model import load_model

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# the packed rehearsal tree of qwen2-7b-ams5 from seed 5, as the maker made
# it before configurations stated their block and chips
PINNED = "5cfb4c6754b790f3c66e7c6693dd9be7a1e4ec71892a553d54b7364df1d61ec3"


def tree_sha256(tree) -> str:
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_one_chip_tree_is_pinned():
    m = load_model("qwen2-7b-ams5", rehearse=True)
    params = system.build_params(m, system.program_config(m), seed=5)
    assert tree_sha256(params) == PINNED


# Two CPU devices exist only in a process that asks for them before JAX
# starts. The Pallas kernels run in interpret mode there
# (``impl="pallas_interpret"``, the rehearsal lowering), which the program
# accepts on a mesh.
TP2 = """
import dataclasses, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax, numpy as np
import system, traffic
from model import load_model

m2 = load_model("qwen2-7b-ams5", rehearse=True, chips=2)
m1 = dataclasses.replace(m2, chips=1)
assert (m2.impl, m2.cache_impl) == ("pallas_interpret", "pallas_interpret")
cfg = system.program_config(m2)
p1 = system.build_params(m1, cfg, 7)
p2 = system.build_params(m2, cfg, 7)
for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(p1)[0],
                        jax.tree.leaves(p2)):
    name = jax.tree_util.keystr(path)
    assert (a.shape, a.dtype) == (b.shape, b.dtype), name
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
    if name.endswith(("['hi']", "['lsb']", "['scale']")):
        assert "model" in b.sharding.spec and len(b.sharding.device_set) == 2, name
tr = traffic.rehearsal(traffic.load_traffic("decode"))
served = {{}}
for m, p in ((m1, p1), (m2, p2)):
    eng = system.make_engine(m, tr, p)
    assert eng.mesh.shape["model"] == m.chips
    mix = traffic.Mix(tr, 7, m.vocab)
    for _ in range(tr["clients"]):
        r = mix.next_closed()
        eng.submit(r.prompt, max_tokens=r.max_tokens)
    eng.run()
    served[m.chips] = [list(map(int, r.tokens)) for r in eng.finished]
assert served[1] == served[2] and sum(map(len, served[1])) >= 20, served
print("SAME")
"""


def test_two_chip_tree_and_tokens_equal_one_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = textwrap.dedent(TP2.format(bench=BENCH,
                                      src=os.path.join(ROOT, "src")))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and out.stdout.strip().endswith("SAME"), \
        out.stderr[-4000:]
