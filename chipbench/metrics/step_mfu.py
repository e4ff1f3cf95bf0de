"""step_mfu (%): useful model FLOPs of the traced ticks (linears over the
tokens fed, attention over the keys each fed query attends, the LM head
over active slots; `work.tick_work`) over the traced window times the
cell's chips times one chip's peak bf16 FLOP/s: `tick_work` gives one
chip's share of the FLOPs, so it is divided by one chip's peak. Moves
tpot_ms. It bounds what the kernels' roofline shares can claim end to
end."""

import devtrace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    w = devtrace.window(run.trace)
    work = run.traced_work()
    if w is None or not work:
        return None
    return 100.0 * work["step_flops"] / ((w[1] - w[0]) * run.peak["bf16_flops_per_s"])
