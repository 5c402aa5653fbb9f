"""The end of a user's run: ``device_get`` of the whole carry and
``JaxEngine._assemble`` (harness span)."""


def read(r):
    return r.spans.get("loop.fetch_s")
