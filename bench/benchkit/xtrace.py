"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

`read_trace` reads the newest ``.xplane.pb`` under a directory with
`jax.profiler.ProfileData` and returns a `Trace`: the operations that
ran on each device (the ``XLA Ops`` line of every ``/device:TPU:n``
plane) and the host annotations the benchmark wrote (names starting
with ``bench:``), all in seconds on the trace's clock.  `Trace` then
gives the busy union, the idle share, kernel time by name, the longest
operations and the longest idle gaps, each gap named by the annotation
the host was inside.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    devices: dict          # plane name -> [(op name, t0, t1)]
    host: list             # [(annotation without prefix, t0, t1)]

    def window(self) -> tuple[float, float]:
        """The span of the host annotation ``window`` (the measured
        window), or of the whole trace when there is none."""
        for n, t0, t1 in self.host:
            if n == "window":
                return t0, t1
        ts = [t for evs in self.devices.values() for _, a, b in evs
              for t in (a, b)] + [t for _, a, b in self.host for t in (a, b)]
        return (min(ts), max(ts)) if ts else (0.0, 0.0)

    def busy_s(self, win: tuple[float, float]) -> float:
        """Seconds in ``win`` in which some operation ran, averaged over
        the devices."""
        if not self.devices:
            return 0.0
        return sum(_length(_union(evs, win)) for evs in
                   self.devices.values()) / len(self.devices)

    def kernel_events(self, pattern: str,
                      win: tuple[float, float]) -> list[tuple[str, float]]:
        """``(instruction text, duration)`` of the operations whose name
        (`op_name`, not the operands) matches ``pattern`` and that start
        in ``win``, over every device."""
        rx = re.compile(pattern)
        return [(n, b - a) for evs in self.devices.values()
                for n, a, b in evs
                if rx.search(op_name(n)) and win[0] <= a < win[1]]

    def top_ops(self, win: tuple[float, float], n: int = 10) -> list:
        """``[[name, seconds], ...]``: device self time per operation
        name (an operation that encloses others, such as a loop, is
        charged only its own time), most first, averaged over the
        devices."""
        tot: dict[str, float] = {}
        for evs in self.devices.values():
            for name, own in _self_times(evs, win):
                tot[name] = tot.get(name, 0.0) + own / len(self.devices)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, win: tuple[float, float], n: int = 10) -> list:
        """``[[name, seconds], ...]``: the longest stretches in which the
        first device ran nothing, each named by the innermost benchmark
        annotation around its midpoint."""
        if not self.devices:
            return []
        evs = self.devices[sorted(self.devices)[0]]
        gaps, t = [], win[0]
        for a, b in _union(evs, win):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if win[1] > t:
            gaps.append((t, win[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_at((a + b) / 2), b - a] for a, b in gaps[:n]]

    def host_at(self, t: float) -> str:
        inside = [(b - a, n) for n, a, b in self.host
                  if a <= t <= b and n != "window"]
        return min(inside)[1] if inside else "outside requests"


def op_name(hlo: str) -> str:
    """``%selection_counts_pallas.18 = s32[...] custom-call(...)`` ->
    ``selection_counts_pallas``: the instruction name without its
    numbering, the same for every call of one operation."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _self_times(evs, win) -> list[tuple[str, float]]:
    """Each event's time inside ``win`` less the events nested in it."""
    out: list[list] = []
    stack: list[list] = []
    for name, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
        lo, hi = max(a, win[0]), min(b, win[1])
        if hi <= lo:
            continue
        while stack and stack[-1][2] <= lo:
            stack.pop()
        item = [op_name(name), lo, hi, hi - lo]
        if stack and hi <= stack[-1][2]:
            stack[-1][3] -= hi - lo
        stack.append(item)
        out.append(item)
    return [(name, own) for name, _, _, own in out]


def _union(evs, win) -> list[tuple[float, float]]:
    spans = sorted((max(a, win[0]), min(b, win[1])) for _, a, b in evs
                   if min(b, win[1]) > max(a, win[0]))
    out: list[list[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(spans) -> float:
    return sum(b - a for a, b in spans)


def latest_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_trace(path: str) -> Trace:
    """Parse one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name[len(PREFIX):], e.start_ns * 1e-9,
                             e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(PREFIX))
    return Trace(devices, host)
