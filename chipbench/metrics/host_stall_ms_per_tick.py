"""host_stall_ms_per_tick (ms): mean per traced tick of the device-idle time
(no device operation running) that lies inside the engine's phase spans
other than ``engine.wait``, placed on the trace's timeline (`progspans`).
Moves tpot_ms: the host work that keeps the chip waiting. It reads at most
host_ms_per_tick, less where host work overlaps the device's."""

import progspans


def read(run):
    ticks = progspans.placed(run)
    if not ticks or not run.trace.ops:
        return None
    return 1e3 * progspans.stall_seconds(run.trace, ticks) / len(ticks)
