"""Device self milliseconds a window spends in the app's batched step
(``window.compute``), from the ops' named scope."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.compute")
