"""device_idle_share (%): 1 - (union of device-operation intervals / traced
window), averaged over the chips used. Moves tpot_ms: while the device
idles, no token is computed."""

import devtrace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    w = devtrace.window(run.trace)
    busy = devtrace.busy_seconds(run.trace)
    if w is None or busy is None:
        return None
    return 100.0 * (1.0 - busy / (w[1] - w[0]))
