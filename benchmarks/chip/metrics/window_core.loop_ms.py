"""Device self milliseconds a window spends in chunk ops under no window
phase: the ops XLA's loop passes emit under the scan's own ``while`` name
(``outside`` in ``program_spans.py``). On the chip they are the rings'
relayouts and copies; the snapshot write is a select under
``window.snapshot``, read by ``window_core.snapshot_ms``."""

from program_spans import outside_ms_per_window


def read(r):
    return outside_ms_per_window(r)
