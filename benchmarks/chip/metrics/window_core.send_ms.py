"""Device self milliseconds a window spends staging and sending
(``window.send``: the per-duct latency draw, fault masks, the drop-iff-full
decision and the sender counters), from the ops' named scope."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.send")
