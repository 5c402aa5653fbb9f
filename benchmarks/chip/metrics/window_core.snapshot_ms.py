"""Device self milliseconds a window spends in the QoS snapshot's ops
under ``window.snapshot`` (inside ``window.close``), from the ops' named
scope: the snapshot row and its write into the carried buffer, a masked
select over the buffer."""

from program_spans import phase_ms_per_window


def read(r):
    return phase_ms_per_window(r, "window.snapshot")
