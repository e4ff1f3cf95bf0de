"""paged_attn_roofline (%): one chip's share of the least time of the
traced ticks' attention (`work.tick_work`: 4 x heads x head_dim FLOPs per
query-key pair, and the packed K/V bytes of the keys each slot attends, the
whole model's divided by the cell's chips, which hold its kv heads evenly)
over one chip's device time of the paged attention template's Pallas
kernel (`devtrace.kernel_seconds`: averaged over the chips traced). Moves
tpot_ms.

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
