"""The profiler's trace of a run's window, and its reduction to device
busy time, kernel time, collective time not hidden behind compute, and
idle gaps named by what the host was doing.

The benchmark writes the host spans itself (``bench.prefill``,
``bench.decode``, ``bench.feed``, ``bench.step``, and ``bench.window``
over the whole window) with ``jax.profiler.TraceAnnotation``, so they sit
on the same clock as the device's operations.  Device operations are the
events of each ``/device:TPU:<n>`` plane's ``XLA Ops`` line.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
# an op event is named by its HLO text, "%name = <shape> opcode(...)..."
HLO = re.compile(r"^%?([\w.-]+) = .*?[\]})] ([a-z][a-z0-9-]*)\(")
# ops that hold other ops: their events span their bodies' ops and the
# gaps between them, so they are left out
CONTAINERS = {"while", "conditional", "call"}
# host spans looked at before a time when naming it (spans nest this deep)
NEST = 8


class Tracer:
    """Starts and stops the profiler around a run's window."""

    def __init__(self, path: str):
        self.path = path
        self.active = False
        self._window = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no Python call events
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self.active = True
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def stop(self) -> None:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self) -> "Reduced":
        files = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no .xplane.pb under {self.path}")
        from jax.profiler import ProfileData
        red = reduce_profile(ProfileData.from_file(files[0]))
        shutil.rmtree(self.path, ignore_errors=True)
        return red


@dataclasses.dataclass
class Op:
    name: str     # the event's name: for a TPU op, its HLO text
    label: str    # instruction name and opcode
    opcode: str
    start: int    # ns
    end: int


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]                  # ns, the bench.window span
    devices: dict[str, list[Op]]             # clipped to the window
    host: list[tuple[str, int, int]]         # bench.* spans in the window

    def __post_init__(self):
        self.host = sorted(self.host, key=lambda h: h[1])
        self._starts = [a for _, a, _ in self.host]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(_total(_merge((o.start, o.end) for o in ops))
                   for ops in self.devices.values()) * 1e-9 \
            / len(self.devices)

    def kernel_ops(self, pattern: str) -> list[Op]:
        """Ops whose event name (HLO text) matches ``pattern``."""
        rx = re.compile(pattern)
        hit: dict[str, bool] = {}
        out = []
        for ops in self.devices.values():
            for o in ops:
                if o.name not in hit:
                    hit[o.name] = bool(rx.search(o.name))
                if hit[o.name]:
                    out.append(o)
        return out

    def exposed_collective_s(self) -> float:
        """Per device: time in collective operations during which no other
        operation runs; averaged over devices."""
        if not self.devices:
            return 0.0
        tot = 0
        for ops in self.devices.values():
            coll = _merge((o.start, o.end) for o in ops if _is_coll(o))
            comp = _merge((o.start, o.end) for o in ops if not _is_coll(o))
            tot += _total(coll) - _overlap(coll, comp)
        return tot * 1e-9 / len(self.devices)

    def idle_gaps(self) -> list[tuple[str, float, float]]:
        """(host activity, start s, length s) of every gap in which no
        operation ran on the first device."""
        if not self.devices:
            return []
        ops = self.devices[sorted(self.devices)[0]]
        busy = _merge((o.start, o.end) for o in ops)
        gaps, t = [], self.window[0]
        for a, b in busy + [(self.window[1], self.window[1])]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        return [(self.host_at((a + b) // 2), (a - self.window[0]) * 1e-9,
                 (b - a) * 1e-9) for a, b in gaps]

    def host_at(self, t: int) -> str:
        """The innermost bench span covering t, else the host outside the
        benchmark's calls (the scheduler, or the training loop)."""
        best = None
        i = bisect.bisect_right(self._starts, t) - 1
        for name, a, b in self.host[max(i - NEST, 0):i + 1]:
            if a <= t < b and (best is None or b - a < best[1]):
                best = (name, b - a)
        return best[0] if best else "host outside bench calls"

    def breakdown(self, top: int = 10) -> dict:
        per = collections.Counter()
        for ops in self.devices.values():
            for o in ops:
                per[o.label] += o.end - o.start
        n = max(len(self.devices), 1)
        device_ops = [[k, v * 1e-9 / n] for k, v in per.most_common(top)]
        idle = collections.Counter()
        for who, _, length in self.idle_gaps():
            idle[who] += length
        return {"device_ops": device_ops,
                "idle_gaps": [[k, v] for k, v in idle.most_common(top)]}


def _is_coll(o: Op) -> bool:
    return bool(COLLECTIVE.match(o.opcode))


def _merge(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _total(merged) -> int:
    return sum(b - a for a, b in merged)


def _overlap(xs, ys) -> int:
    """Length of the intersection of two merged interval lists."""
    starts = [a for a, _ in ys]
    tot = 0
    for a, b in xs:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(ys) and ys[i][0] < b:
            lo, hi = max(a, ys[i][0]), min(b, ys[i][1])
            tot += max(0, hi - lo)
            i += 1
    return tot


def _parse(name: str) -> tuple[str, str]:
    """(label, opcode) of an op event: "while.13 (while)" and "while"
    from its HLO text; a name that is no HLO text is both."""
    m = HLO.match(name)
    if not m:
        return name, name
    return f"{m.group(1)} ({m.group(2)})", m.group(2)


def reduce_profile(pd) -> Reduced:
    """Device operations and bench host spans inside the ``bench.window``
    span of a ``jax.profiler.ProfileData``."""
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)))
        elif DEVICE_PLANE.match(plane.name):
            parsed: dict[str, tuple[str, str]] = {}
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if e.name not in parsed:
                        parsed[e.name] = _parse(e.name)
                    if parsed[e.name][1] in CONTAINERS:
                        continue
                    a = int(e.start_ns)
                    ops.append(Op(e.name, *parsed[e.name], a,
                                  a + int(e.duration_ns)))
            devices[plane.name] = ops
    wins = [(a, b) for n, a, b in host if n == WINDOW]
    if not wins:
        raise RuntimeError("the trace has no bench.window span")
    w0, w1 = wins[0]
    clipped = {k: [Op(o.name, o.label, o.opcode, max(o.start, w0),
                      min(o.end, w1))
                   for o in ops if o.end > w0 and o.start < w1]
               for k, ops in devices.items()}
    host = [(n, a, b) for n, a, b in host
            if n != WINDOW and b > w0 and a < w1]
    return Reduced((w0, w1), clipped, host)
