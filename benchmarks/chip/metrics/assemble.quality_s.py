"""Host seconds of the app's quality count in the user's result (program span
``assemble.quality`` around ``bapp.quality`` in ``JaxEngine._assemble``)."""

from program_spans import span_s


def read(r):
    return span_s("assemble.quality")
