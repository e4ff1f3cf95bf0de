"""paged_attn_roofline (%): least time of the traced ticks' attention
(`work.tick_work`: 4 x heads x head_dim FLOPs per query-key pair, and the
packed K/V bytes of the keys each slot attends) over the device time of
the paged attention template's Pallas kernel. Moves tpot_ms.

Its ``pallas_call`` carries no name: in a v5e trace it runs as a
``closed_call`` instruction that is a ``tpu_custom_call``, once per layer
per tick (read by hand from this benchmark's trace of
qwen2-7b-ams5.decode, where the step's only other Pallas kernel is
``ams_matmul_padded``)."""

import devtrace

KERNEL = (r"^%closed_call\.\d+ tpu_custom_call$",)


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    t = devtrace.kernel_seconds(run.trace, KERNEL)
    work = run.traced_work()
    if not t or not work:
        return None
    return 100.0 * work["attn_least_s"] / t
