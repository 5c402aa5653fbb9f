"""Device self milliseconds a window spends in the drain phase
(``window.drain``: ``duct_window`` with the ring relayouts around it, or the
superstep's base-prefix and pushbuf walk), from the ops' named scope."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.drain")
