"""A run's ``correct`` with the timed path broken underneath it.

Each case drives the whole harness (`run.main`: set-up, window, sample,
reference) at rehearsal size on the CPU, with a fault planted in the
program's serving path, and requires ``correct`` to come out false; the
sound case requires it true. The faults a one-chip serving cell can have:

* ``token``: a served token altered where the step produces it;
* ``state``: the step returns its KV state unchanged (no page is written).

The other faults of a run (half a batch left out of a mean, the exchange
between chips left out) belong to training and to cells on several chips.
"""

import json

import pytest

import run


def _token_fault(monkeypatch):
    import repro.launch.sampling as sampling
    orig = sampling.sample_tokens

    def altered(logits, samp):
        tok, done = orig(logits, samp)
        return (tok + 1) % logits.shape[-1], done
    monkeypatch.setattr(sampling, "sample_tokens", altered)


def _state_fault(monkeypatch):
    import repro.cache as cache
    monkeypatch.setattr(cache, "paged_insert",
                        lambda pool, *a, **k: pool)


FAULTS = {"sound": None, "token": _token_fault, "state": _state_fault}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_correct_catches_fault(fault, monkeypatch, capsys):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    run.main(["--workload", "qwen2-7b-ams5.decode", "--seed", "3000000019", "--seconds", "4",
              "--trace", "0", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is (fault == "sound"), line["checks"]
