"""Host seconds of ``JaxEngine.__init__`` (program span ``setup.engine``:
the batched app, the edge tables with ``halo_slot_map``, the layout plan and
the device tables)."""

from program_spans import span_s


def read(r):
    return span_s("setup.engine")
