"""host_ms_per_tick (ms): mean, over the traced ticks, of the host work in
each tick: the engine's own phase spans other than ``engine.wait``
(`progspans`). Moves tpot_ms: while the host works the tick is not over."""

import progspans


def read(run):
    ticks = progspans.placed(run)
    if not ticks:
        return None
    return 1e3 * sum(map(progspans.host_seconds, ticks)) / len(ticks)
