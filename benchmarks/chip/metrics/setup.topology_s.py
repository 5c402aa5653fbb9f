"""Host seconds of the topology builder (program span ``setup.topology``
around ``make_topology``)."""

from program_spans import span_s


def read(r):
    return span_s("setup.topology")
