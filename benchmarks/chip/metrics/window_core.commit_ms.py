"""Device self milliseconds a window spends committing the superstep's
pushes into the rings (``window.commit``: ``duct_commit``, once per
superstep), from the ops' named scope."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.commit")
