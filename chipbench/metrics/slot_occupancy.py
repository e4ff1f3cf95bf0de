"""slot_occupancy (%): mean share of the engine's slots serving a request,
over the window's ticks (read from the engine's slot table after each
tick's admission). Moves output_tok_s: an idle slot is a decode row that
delivers nothing."""


def read(run):
    ticks = run.window_ticks()
    if not ticks:
        return None
    return 100.0 * sum(t.active for t in ticks) / (len(ticks) * run.slots)
