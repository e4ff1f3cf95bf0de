"""ams_matmul_roofline (%): least time of the traced ticks' packed linears
(`work.tick_work`: max of FLOPs over peak FLOP/s and packed weight plus
activation bytes over peak bandwidth, per linear) over the device time of
the ``ams_matmul`` Pallas kernel. Moves tpot_ms.

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
