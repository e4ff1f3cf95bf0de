"""Entry points: the serve CLI's width switch, the compile-cache placement,
and the engine's acceptance of an already-packed weight tree.

  * `repro.launch.serve`'s parser yields ``reduced=False`` for
    ``--no-reduced`` (the flag used to be ``store_true`` with a True
    default, so published widths were unreachable from the CLI);
  * `enable_compile_cache` leaves a set ``JAX_COMPILATION_CACHE_DIR`` to
    JAX and otherwise points JAX at one fixed, git-ignored directory of
    the checkout;
  * ``ServeEngine(params=<packed tree>)`` serves the same streams as
    letting the engine build and quantize the same seed — its bf16 cast
    touches only float weights, never the int planes or f32 scales.
"""

import pathlib

import jax
import numpy as np
import pytest

from repro.cache import CacheConfig
from repro.configs import get_config
from repro.core.policy import QuantPolicy
from repro.launch import compile_cache
from repro.launch.config import EngineConfig
from repro.launch.engine import ServeEngine
from repro.launch.serve import build_parser
from repro.models import init_serving_params

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_serve_cli_reduced_switch():
    assert build_parser().parse_args([]).reduced is True
    assert build_parser().parse_args(["--reduced"]).reduced is True
    assert build_parser().parse_args(["--no-reduced"]).reduced is False


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # set nothing


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("kind", ["paged_ams", "contiguous"])
def test_engine_accepts_packed_params(kind):
    scheme = "fp5.33-e2m3"
    cache = CacheConfig(kind=kind) if kind != "contiguous" else None
    ec = EngineConfig(arch="qwen2-7b", scheme=scheme, slots=2, capacity=24,
                      prefill_chunk=4, cache=cache, seed=3)
    packed = init_serving_params(
        jax.random.PRNGKey(3), get_config("qwen2-7b").reduced(),
        quant=QuantPolicy(scheme=scheme, min_elements=1 << 10))
    streams = []
    for params in (None, packed):
        eng = ServeEngine(ec, params=params)
        for leaf, want in zip(jax.tree.leaves(eng.params),
                              jax.tree.leaves(packed)):
            assert leaf.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
        for p in ([5, 9, 2, 7, 1, 3], [11, 4, 8]):
            eng.submit(np.asarray(p, np.int32), max_tokens=6)
        eng.run()
        streams.append([r.tokens for r in sorted(eng.finished,
                                                 key=lambda r: r.rid)])
    assert streams[0] == streams[1]
