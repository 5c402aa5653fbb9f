"""Compiling the cell's chunk program, or loading it from the persistent
cache (harness span around ``lower().compile()``)."""


def read(r):
    return r.spans.get("setup.compile_s")
