"""ams_matmul_roofline (%): one chip's share of the least time of the traced
ticks' packed linears (`work.tick_work`: max of FLOPs over peak FLOP/s and
packed weight plus activation bytes over peak bandwidth, per linear, the
whole model's divided by the cell's chips) over one chip's device time of
the ``ams_matmul`` Pallas kernel (`devtrace.kernel_seconds`: averaged over
the chips traced). Moves tpot_ms.

In a v5e trace the kernel runs as the ``tpu_custom_call`` instruction of
the jitted ``ams_matmul_padded`` (read by hand from this benchmark's
trace of qwen2-7b-ams5.decode)."""

import devtrace

KERNEL = (r"^%ams_matmul_padded\.\d+ tpu_custom_call$",)


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    t = devtrace.kernel_seconds(run.trace, KERNEL)
    work = run.traced_work()
    if not t or not work:
        return None
    return 100.0 * work["matmul_least_s"] / t
