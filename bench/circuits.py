"""The circuits of a configuration, made by the program's generators and
held to the digests the configuration file pins.

A configuration file names, for each suite, the generator of
``repro.core.circuits`` that makes it (a suite, or one circuit) and that
generator's arguments, and
pins the content digest (:func:`bench.reference.netlist.digest`) of every
netlist it must make.  A netlist that does not match its pin ends the run
without a result: the yardstick must not move under a change to the
generators.
"""
from __future__ import annotations

from bench.harness import BenchError
from bench.reference.netlist import digest


class DigestMismatch(BenchError):
    """A generated netlist differs from its pinned digest."""


def _generate(name: str, kwargs: dict) -> list:
    """Call a generator of ``repro.core.circuits``; one netlist or a list."""
    from repro.core import circuits

    out = getattr(circuits, name)(**kwargs)
    return out if isinstance(out, list) else [out]


class Designs:
    """The netlists of one configuration, built once on first use."""

    def __init__(self, config: dict):
        self.config = config
        self._suites: dict | None = None

    def suites(self) -> dict:
        """``{suite name: [netlists]}`` in the file's order, each netlist
        checked against its pinned digest."""
        if self._suites is None:
            out = {}
            for s in self.config["suites"]:
                nets = _generate(s["generator"], s["kwargs"])
                pins = s["circuits"]
                got = {n.name: digest(n) for n in nets}
                if list(got) != list(pins):
                    raise DigestMismatch(
                        f"{self.config['name']}/{s['suite']}: generator made "
                        f"{list(got)}, the configuration pins {list(pins)}")
                bad = [k for k in pins if pins[k] != got[k]]
                if bad:
                    raise DigestMismatch(
                        f"{self.config['name']}/{s['suite']}: netlists "
                        f"{bad} differ from their pinned digests")
                out[s["suite"]] = nets
            self._suites = out
        return self._suites

    def circuits(self) -> list:
        return [n for ns in self.suites().values() for n in ns]
