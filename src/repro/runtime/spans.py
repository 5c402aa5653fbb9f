"""Host spans and counters of the program's phases.

The program's only tracing API. ``span(name)`` times a phase on the host
clock and, where a ``jax.profiler`` session is recording, writes the same
span to the profiler's host plane, on the clock of the device trace::

    with span("setup.engine"):
        with span("edges"):          # recorded as "setup.engine/edges"
            ...
    count("setup.ducts", E)

    @span("setup.carry")
    def _init_carry(self, seed): ...

A span is keyed by its path: the names of the spans open around it on
the same thread, joined by ``/``. The table keeps, per path, the calls
and the seconds they took; a span's self time is its seconds less its
direct children's. ``count`` adds to a counter. ``totals()``,
``counters()`` and ``reset()`` read and clear both tables, which hold
everything the process has recorded; ``report()`` prints them, as the
experiments CLI does under ``--trace-dir``.

Spans and counters go at phase boundaries only, never inside a
per-process or per-edge loop: a loop's size goes into one ``count`` after
it. Without a profiler session a span costs two ``perf_counter`` calls
and a dict update.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Tuple

_lock = threading.Lock()
_local = threading.local()
_totals: Dict[str, list] = {}       # path -> [calls, seconds]
_counters: Dict[str, int] = {}


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str):
    """Time the enclosed phase under ``name``; usable as a decorator."""
    # imported here so that host-only modules (the topology builders) can
    # carry spans without importing JAX
    from jax.profiler import TraceAnnotation
    stack = _stack()
    path = f"{stack[-1]}/{name}" if stack else name
    stack.append(path)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(path):
            yield
    finally:
        dt = time.perf_counter() - t0
        stack.pop()
        with _lock:
            entry = _totals.setdefault(path, [0, 0.0])
            entry[0] += 1
            entry[1] += dt


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def totals() -> Dict[str, Tuple[int, float]]:
    """``{path: (calls, seconds)}`` of every span recorded."""
    with _lock:
        return {p: (c, s) for p, (c, s) in _totals.items()}


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Clear both tables."""
    with _lock:
        _totals.clear()
        _counters.clear()


def report() -> str:
    """Both tables as text: each span path with its calls, seconds and
    self seconds (less its direct children's), then each counter."""
    t = totals()
    children: Dict[str, float] = {}
    for path, (_, secs) in t.items():
        if "/" in path:
            parent = path.rsplit("/", 1)[0]
            children[parent] = children.get(parent, 0.0) + secs
    lines = [f"{'span':<40} {'calls':>7} {'s':>10} {'self s':>10}"]
    for path in sorted(t):
        calls, secs = t[path]
        lines.append(f"{path:<40} {calls:>7} {secs:>10.4f} "
                     f"{secs - children.get(path, 0.0):>10.4f}")
    lines.append(f"{'counter':<40} {'value':>7}")
    lines += [f"{name:<40} {n:>7}" for name, n in sorted(counters().items())]
    return "\n".join(lines)


def key_compiles_by_names() -> None:
    """Key JAX's persistent compilation cache by op metadata too.

    The window phases' ``jax.named_scope``s live only in the ops'
    metadata, which the cache leaves out of its key by default: a program
    compiled by code with other scopes, or none, would be loaded in place
    of this one, and its trace would carry the old names. A cache kept
    across checkouts (``JAX_COMPILATION_CACHE_DIR``) does exactly that.
    The engines call this before they build a chunk program, since not
    every entry point that compiles one keys its cache so; afterwards an
    edit that moves a line of the engine recompiles the chunk."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
