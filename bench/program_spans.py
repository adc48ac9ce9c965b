"""The program's own spans in a traced run: the ``repro.*`` host events of
the window's profile, with their stats.

The program opens ``jax.profiler.TraceAnnotation`` spans at its layer
boundaries (``repro.eval.plan``, ``repro.pack.cluster``, ...), sizes and
counts attached as the event's stats.  They lie on a host plane of the
``.xplane.pb`` that ``--trace 1`` captures (``harness.TRACE_DIR/
<workload>``), on the device operations' clock.  :class:`bench.trace.Trace`
keeps the benchmark's own ``bench.*`` spans but drops stats; this reads
the program's, stats kept, one parse per profile.

A program without such spans (an earlier commit) gives ``None`` for every
reading, so its metrics are absent, never 0.
"""
from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field

from bench import harness
from bench.trace import DEVICE_PLANE, Capture

PREFIX = "repro."

#: one parse per profile file
_PARSED: dict[tuple, "Spans"] = {}


@dataclass
class Spans:
    """Sorted ``(start_ns, end_ns, stats)`` of each ``repro.*`` span
    name."""

    by_name: dict = field(default_factory=dict)

    @classmethod
    def from_rows(cls, rows):
        """From ``(plane, line, name, start_ns, duration_ns, stats)``
        rows, ``stats`` a mapping of the event's arguments."""
        sp = cls()
        for plane, _line, name, start, dur, stats in rows:
            if not name.startswith(PREFIX) or DEVICE_PLANE.match(plane):
                continue
            sp.by_name.setdefault(name, []).append(
                (float(start), float(start) + float(dur), dict(stats)))
        for spans in sp.by_name.values():
            spans.sort(key=lambda s: (s[0], s[1]))
        return sp

    def seconds(self, *names) -> float | None:
        """Seconds under any span of ``names``: the union of their
        intervals, so a span inside another of them counts once.  None
        where the profile has no such span."""
        rows = sorted((s, e) for n in names for s, e, _ in
                      self.by_name.get(n, ()))
        if not rows:
            return None
        total = 0.0
        cur_s, cur_e = rows[0]
        for s, e in rows[1:]:
            if s <= cur_e:
                cur_e = max(cur_e, e)
            else:
                total += cur_e - cur_s
                cur_s, cur_e = s, e
        total += cur_e - cur_s
        return total / 1e9

    def stat_sum(self, name: str, key: str) -> float | None:
        """Sum of one stat over the spans of ``name`` that carry it; None
        where none does."""
        vals = [st[key] for _, _, st in self.by_name.get(name, ())
                if key in st]
        return sum(vals) if vals else None


def rows_of(path: str):
    """Every ``repro.*`` event of a profile, stats kept."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    with warnings.catch_warnings():
        # the profile's stats type warns on the way out on some Pythons
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        yield (plane.name, line.name, e.name, e.start_ns,
                               e.duration_ns, dict(e.stats))


def load(run, root: str = harness.ROOT) -> Spans | None:
    """The spans of the run's profile, ``root/harness.TRACE_DIR/
    <workload>``; None in an untraced run or where there is no profile."""
    if run.trace is None:
        return None
    try:
        path = Capture(os.path.join(root, harness.TRACE_DIR,
                                    run.workload)).path()
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _PARSED:
        _PARSED[key] = Spans.from_rows(rows_of(path))
    return _PARSED[key]


def root_of(reader_file: str) -> str:
    """The checkout a reader ``<root>/bench/metrics/<name>.py`` lies in:
    the harness loads each reader by path from the tree it runs."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))


def window_share(run, root: str, *names) -> float | None:
    """Percent of the run's window under spans of ``names``."""
    sp = load(run, root)
    if sp is None or run.window_s <= 0:
        return None
    s = sp.seconds(*names)
    return None if s is None else 100.0 * s / run.window_s
