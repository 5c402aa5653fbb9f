"""Device self milliseconds a window spends closing, less the snapshot
scatter nested in it (``window.close``: the step-factor draw, termination,
barriers and the clock advance), from the ops' named scope."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.close")
