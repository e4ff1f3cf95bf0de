"""step_ms (ms): the traced window divided by the engine-step programs that
ran on the device in it: the period of one tick, device work and host gaps
together. Moves tpot_ms."""

import devtrace

STEP_PROGRAM = r"engine_fn"


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    w = devtrace.window(run.trace)
    n = devtrace.module_runs(run.trace, STEP_PROGRAM)
    if w is None or n == 0:
        return None
    return 1e3 * (w[1] - w[0]) / n
