"""Capture of a profiler trace around the window, and its reduction to
device busy time, per-program and per-kernel device time, and idle gaps.

The reduction reads JAX's own ``.xplane.pb`` through
``jax.profiler.ProfileData``.  Device planes are named ``/device:TPU:<n>``;
on each, the ``XLA Modules`` line has one event per program run and the
``XLA Ops`` line one event per operation.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) lie on a host plane
on the same clock.  Stable program and kernel names are kept as data in
``bench/programs/<name>.json``: regular expressions over the module and
operation names as the trace prints them.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import shutil
import time
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the benchmark writes around its calls into the program
SPAN_PREFIX = "bench."
#: the opcode of an operation as the trace prints it (``%x.1 = <shape>
#: opcode(<operands>)``), and a custom call's target
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\((?:[a-z]+\d*\[|\(|%|\))")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: operations whose time is that of the operations they contain
CONTAINERS = {"while", "conditional", "call"}

PROGRAMS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "programs")


def program_patterns(name: str) -> dict:
    """``{"modules": [regex], "ops": [regex]}`` of one stable program or
    kernel name (``bench/programs/<name>.json``)."""
    with open(os.path.join(PROGRAMS_DIR, f"{name}.json")) as f:
        return json.load(f)


def stable_module_name(module: str) -> str:
    """The stable name of a program run: the ``bench/programs`` file whose
    module patterns match it, else the module name without its hash."""
    for fname in sorted(os.listdir(PROGRAMS_DIR)):
        name = fname[:-len(".json")]
        if any(re.search(p, module)
               for p in program_patterns(name).get("modules", ())):
            return name
    return module.split("(")[0]


class Capture:
    """``jax.profiler`` trace of one window, host Python tracing off."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.wall_s = 0.0

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.wall_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]

    def cleanup(self) -> None:
        shutil.rmtree(self.log_dir, ignore_errors=True)


@dataclass
class Trace:
    """The reduced trace: per device, sorted ``(start_ns, end_ns, name)``
    of operations and of program runs; the benchmark's host spans."""

    ops: dict = field(default_factory=dict)        # device -> [(s, e, n)]
    modules: dict = field(default_factory=dict)    # device -> [(s, e, n)]
    spans: list = field(default_factory=list)      # [(s, e, name)]
    window_s: float = 0.0
    n_chips: int = 1

    @classmethod
    def from_events(cls, events, window_s: float, n_chips: int = 1):
        """From plain ``(plane, line, name, start_ns, duration_ns)`` rows
        — what :meth:`load` reads from a trace file, and what a recorded
        trace's extract holds."""
        t = cls(window_s=window_s, n_chips=n_chips)
        for plane, line, name, start, dur in events:
            m = DEVICE_PLANE.match(plane)
            row = (float(start), float(start) + float(dur), name)
            if m:
                dev = int(m.group(1))
                if dev >= n_chips:
                    continue
                if line == OPS_LINE:
                    t.ops.setdefault(dev, []).append(row)
                elif line == MODULES_LINE:
                    t.modules.setdefault(dev, []).append(row)
            elif name.startswith(SPAN_PREFIX):
                t.spans.append(row)
        for d in (t.ops, t.modules):
            for rows in d.values():
                rows.sort()
        t.spans.sort()
        return t

    @staticmethod
    def events_of(path: str):
        """Every event of a trace file as plain rows."""
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    yield (plane.name, line.name, e.name, e.start_ns,
                           e.duration_ns)

    @classmethod
    def load(cls, path: str, window_s: float, n_chips: int = 1):
        return cls.from_events(cls.events_of(path), window_s, n_chips)

    # -- reductions ---------------------------------------------------------

    @staticmethod
    def _union(rows) -> list[tuple[float, float]]:
        out: list[list[float]] = []
        for s, e, _ in rows:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        total = 0.0
        for dev in range(self.n_chips):
            total += sum(e - s for s, e in self._union(self.ops.get(dev, [])))
        return total / max(self.n_chips, 1) / 1e9

    def idle_share(self) -> float | None:
        if self.window_s <= 0:
            return None
        return max(0.0, 1.0 - self.busy_s() / self.window_s)

    def op_seconds(self, patterns) -> float:
        """Summed device time of the operations matching any pattern,
        averaged over the chips."""
        rx = [re.compile(p) for p in patterns]
        total = sum(e - s for rows in self.ops.values() for s, e, n in rows
                    if any(r.search(n) for r in rx))
        return total / max(self.n_chips, 1) / 1e9

    def module_seconds(self, patterns) -> float:
        rx = [re.compile(p) for p in patterns]
        total = sum(e - s for rows in self.modules.values()
                    for s, e, n in rows if any(r.search(n) for r in rx))
        return total / max(self.n_chips, 1) / 1e9

    @staticmethod
    def op_kind(name: str) -> str:
        """``opcode`` of an operation, ``custom-call:<target>`` for a
        custom call; the name itself where the trace prints no HLO."""
        m = OPCODE.search(name)
        if not m:
            return name.split(" = ")[0].lstrip("%")
        kind = m.group(1)
        if kind == "custom-call":
            t = TARGET.search(name)
            kind += f":{t.group(1)}" if t else ""
        return kind

    def _module_of(self, dev: int, t: float, names: dict) -> str:
        rows = self.modules.get(dev, [])
        i = bisect.bisect_right(rows, (t, float("inf"), "")) - 1
        if i >= 0 and rows[i][0] <= t < rows[i][1]:
            m = rows[i][2]
            if m not in names:
                names[m] = stable_module_name(m)
            return names[m]
        return "?"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, by stable program
        name and opcode (operations that contain others left out), and the
        longest
        idle gaps of chip 0, each named by the benchmark span the host
        was in."""
        per_op: dict[str, float] = {}
        names: dict[str, str] = {}
        for dev, rows in self.ops.items():
            for s, e, n in rows:
                kind = self.op_kind(n)
                if kind in CONTAINERS:
                    continue
                key = f"{self._module_of(dev, s, names)}/{kind}"
                per_op[key] = per_op.get(key, 0.0) + (e - s) / 1e9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        busy = self._union(self.ops.get(0, []))
        gaps = [(b0[1], b1[0]) for b0, b1 in zip(busy, busy[1:])]
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for s, e in gaps[:top]:
            mid = (s + e) / 2
            inner = [sp for sp in self.spans if sp[0] <= mid <= sp[1]]
            label = (min(inner, key=lambda sp: sp[1] - sp[0])[2]
                     if inner else "outside bench spans")
            named.append([label, (e - s) / 1e9])
        return {"device_ops": [[n, s / max(self.n_chips, 1)]
                               for n, s in ops],
                "idle_gaps": named}
