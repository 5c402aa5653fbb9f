"""Device self milliseconds a window spends closing, less the snapshot
select nested in it (``window.close``: the step-factor draw, termination,
the barrier release's reductions and the clock advance), from the ops'
named scope."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.close")
