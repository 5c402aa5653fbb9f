"""Host seconds of importing the vectorized engine's modules (program span
``setup.import`` around ``make_engine``'s deferred import: JAX's Pallas and
Mosaic machinery among them)."""

from program_spans import span_s


def read(r):
    return span_s("setup.import")
