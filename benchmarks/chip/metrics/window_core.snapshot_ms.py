"""Device self milliseconds a window spends in the QoS snapshot's ops
under ``window.snapshot`` (inside ``window.close``), from the ops' named
scope. The loop-level write of the carried snapshot buffer, which XLA
names after the scan's ``while``, is not under it: ``window_core.loop_ms``
reads it."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.snapshot")
