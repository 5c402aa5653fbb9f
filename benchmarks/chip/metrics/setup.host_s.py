"""Host set-up: topology, app, engine tables and the carry on the device,
up to ``block_until_ready`` of the carry (harness span)."""


def read(r):
    return r.spans.get("setup.host_s")
