"""Host seconds of the QoS assembly in the user's result (program span
``assemble.qos`` around ``WindowCore.assemble`` in ``JaxEngine._assemble``)."""

from program_spans import span_s


def read(r):
    return span_s("assemble.qos")
