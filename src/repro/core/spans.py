"""Named spans at the flow's layer boundaries, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profile is being
captured it becomes an event of the host plane, on the same clock as the
device's operations, and its stats (sizes and counts, keyword arguments)
come back as the event's stats through ``jax.profiler.ProfileData``.
While no profile is active it costs one annotation object (and, with a
``wall`` dict, one clock pair).  Nothing turns spans on or off.

Span names start with ``repro.`` and never change with the work: sizes
and counts go in the stats.  No span opens inside a per-atom, per-probe
or per-level loop.

    with span("repro.ir.lower", wall, "lower_s", incremental=1):
        ir = packed.lower_ir(template=tpl)

adds the span's seconds to ``wall["lower_s"]`` too, so a program wall
and the span are one measurement.  Stats known only at the end go on
before the span closes:

    with span("repro.timing.build", wall, "build_s") as sp:
        ...
        sp.set(groups=len(progs))
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Span:
    """Context manager of :func:`span`."""

    __slots__ = ("_tm", "_wall", "_key", "_t0")

    def __init__(self, name: str, wall: dict | None, key: str | None,
                 stats: dict):
        if wall is not None and key is None:
            raise ValueError(f"span {name!r}: a wall dict needs its key")
        self._tm = TraceAnnotation(name, **stats)
        self._wall = wall
        self._key = key

    def __enter__(self) -> "Span":
        self._tm.__enter__()
        if self._wall is not None:
            self._t0 = time.perf_counter()
        return self

    def set(self, **stats) -> None:
        """Attach stats known only now; they land on the span's event."""
        self._tm.set_metadata(**stats)

    def __exit__(self, *exc) -> bool:
        if self._wall is not None:
            self._wall[self._key] = (self._wall.get(self._key, 0.0)
                                     + time.perf_counter() - self._t0)
        self._tm.__exit__(*exc)
        return False


def span(name: str, wall: dict | None = None, key: str | None = None,
         **stats) -> Span:
    """A span named ``name`` with ``stats``; with ``wall``, its elapsed
    seconds are added to ``wall[key]``."""
    return Span(name, wall, key, stats)
