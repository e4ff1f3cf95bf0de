"""The control at a size a test run holds: the plain reference computed one
precision step below the configuration (``control_weights``,
``control_kv``), put in the program's place, must come out not correct
under the run's own verdict (`run.judge`) at the cell's rehearsal limit,
while the program on the same prompts and served tokens comes out
correct. The rehearsal limit is set like the chip's, between the program's
readings and the control's at rehearsal size (PERF.md).

On the chip, at the cell's own size, the same readings set the limit
(``calibrate.py``; readings in PERF.md). Here the program serves a short
window of the cell's mix at rehearsal size on the CPU, on three seeds."""

import pytest

import calibrate
import run
import traffic
from model import load_model

SEEDS = (3000000101, 3000000102, 3000000103)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_where_the_program_passes(seed):
    m = load_model("qwen2-7b-ams5", rehearse=True)
    tr = traffic.rehearsal(traffic.load_traffic("decode"))
    limits = run.load_limits("qwen2-7b-ams5.decode", rehearse=True)
    r = calibrate.readings(m, tr, limits, seed, 2.0)
    assert r["tokens"] >= 20, r
    assert r["program_correct"] and not r["control_correct"], r
