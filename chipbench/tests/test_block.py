"""What a configuration file states about its block, as the harness builds
and holds it: q/k/v biases or none, RoPE with linear position scaling, and
the registry entry held to every stated key."""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import model
import reference
import run
import system
from model import load_model

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BIAS_FREE = "deepseek-coder-33b-test"


def test_bias_free_block_runs_the_whole_flow(monkeypatch, capsys):
    """A Llama block without q/k/v biases (DeepSeek-Coder's, as the registry
    holds it) goes through `run.main` at rehearsal size: made without
    ``b`` leaves, served, and within the cell's rehearsal limit."""
    monkeypatch.setattr(model, "CONFIGS", DATA)
    spec = dict(run.load_spec(), workloads=[{
        "name": f"{BIAS_FREE}.decode", "config": BIAS_FREE,
        "traffic": "decode", "chips": 1}])
    monkeypatch.setattr(run, "load_spec", lambda: spec)
    limits = run.load_limits("qwen2-7b-ams5.decode", rehearse=True)
    assert limits == {"logit_gap_mean": 0.007}
    monkeypatch.setattr(run, "load_limits", lambda cell, rehearse: limits)
    m = load_model(BIAS_FREE, rehearse=True)
    assert not m.attention_bias and m.rope_scaling is None
    layers, _ = system.maker(m)
    attn = layers(model.seed_key(1))["sub0"]["attn"]
    assert all("b" not in attn[n] for n in ("wq", "wk", "wv"))
    run.main(["--workload", f"{BIAS_FREE}.decode", "--seed", "3000001701",
              "--seconds", "4", "--trace", "0", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["checks"]["logit_gap_mean"]["value"] <= 0.007


@pytest.mark.parametrize("factor", [4.0, 2.5])
def test_linear_rope_scaling_divides_positions(factor):
    """Factor f at position p is factor 1 at p / f; no scaling is today's
    rotation, bit for bit, and factor 1 is too."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64, 3, 32)),
                    jnp.float32)
    pos = jnp.arange(64)[None, :]
    theta = 100000.0
    scaled = reference._rope(x, pos, theta, ("linear", factor))
    divided = np.arange(64, dtype=np.float32)[None, :] / np.float32(factor)
    np.testing.assert_array_equal(
        scaled, reference._rope(x, jnp.asarray(divided), theta))
    plain = reference._rope(x, pos, theta)
    np.testing.assert_array_equal(
        plain, reference._rope(x, pos, theta, ("linear", 1.0)))
    inv = 1.0 / (theta ** (np.arange(0, 32, 2, dtype=np.float32) / 32))
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :16], x[..., 16:]
    np.testing.assert_array_equal(
        plain, jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1))


def test_rope_scaling_forms():
    assert model.rope_scaling(None) is None
    assert model.rope_scaling({"type": "linear", "factor": 4}) == ("linear", 4.0)
    assert model.rope_scaling(("linear", 4.0)) == ("linear", 4.0)
    with pytest.raises(SystemExit, match="dynamic"):
        model.rope_scaling({"type": "dynamic", "factor": 2.0})


def test_registry_is_held_to_each_stated_key(monkeypatch):
    """A file that states a key the registry entry does not match is
    refused, naming the key: the scaling the registry lacks, a bias it
    has."""
    monkeypatch.setattr(model, "CONFIGS", DATA)
    m = load_model(BIAS_FREE, rehearse=True)
    system.program_config(m)
    scaled = dataclasses.replace(m, rope_scaling=("linear", 4.0))
    with pytest.raises(SystemExit, match=r"rope_scaling: the configuration "
                       r"file states \('linear', 4\.0\), the program's "
                       r"deepseek-coder-33b has None"):
        system.program_config(scaled)
    biased = dataclasses.replace(m, attention_bias=True)
    with pytest.raises(SystemExit, match="attention_bias") as e:
        system.program_config(biased)
    assert "rope_scaling" not in str(e.value)
