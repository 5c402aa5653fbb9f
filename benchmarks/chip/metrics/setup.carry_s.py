"""Host seconds of ``JaxEngine._init_carry`` (program span ``setup.carry``:
the app's per-process initial state and the carry on the device)."""

from program_spans import span_s


def read(r):
    return span_s("setup.carry")
