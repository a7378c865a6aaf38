"""Device idle of a served window split by the program's own spans.

  python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

The program marks the phases of its served path with ``repro.obs.span``:
``repro.decode.inputs``, ``.launch``, ``.sample`` and ``.readback`` inside
each executor decode call, and ``repro.sched.admit``, ``.price`` and
``.retire`` around the scheduler's host work of a step.  They are
``jax.profiler.TraceAnnotation`` spans, so they lie on the clock of the
device's operations, beside the benchmark's ``bench.*`` spans.

:func:`idle_by_program_span` cuts every idle interval of the first device
at the program spans' boundaries and gives each piece to the innermost
span covering it, or to :data:`OUTSIDE`.  :func:`per_decode_call_ms` sums
that idle over the groups in :data:`READINGS` and divides it by the decode
calls in the window.

On a v5e the trace places the device's events up to a few milliseconds
earlier than the host's, by an amount that changes from one profile to
the next, so a split at the trace's own alignment can put one call's idle
in the wrong span.  :func:`device_lead` bounds that lead from the TPU
runtime's own host events (a program starts on the chip after the runtime
launches it, and ends before the runtime sees it done), and the command
also gives the split with the device's events moved later by each bound.

The command serves one window of the cell under the profiler, as
``bench/run.py --trace 1`` does, and prints all of it as one JSON line.
``bench/run.py`` does not report these readings: its reduction
(``tracing.reduce_profile``) keeps only the ``bench.*`` host spans.

Without a TPU, or with fewer chips than the cell asks for, it exits 2.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import tracing  # noqa: E402

PREFIX = "repro."
LAUNCH = "repro.decode.launch"
READBACK = "repro.decode.readback"
# the TPU runtime's host events for launching a program on the chip, and
# for seeing it complete; the line of a device plane that holds whole
# programs
EXECUTE = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
MODULES = "XLA Modules"
OUTSIDE = "outside program spans"
# reading -> name prefixes of the spans whose idle it sums
READINGS = {
    "serve.decode_launch_idle_ms": ("repro.decode.inputs",
                                    "repro.decode.launch"),
    "serve.decode_readback_idle_ms": ("repro.decode.sample",
                                      "repro.decode.readback"),
    "serve.sched_idle_ms": ("repro.sched.",),
}


def host_events(pd, window, keep) -> list[tuple[str, int, int]]:
    """(name, start ns, end ns) of the host events of a
    ``jax.profiler.ProfileData`` whose name ``keep`` accepts and that
    overlap ``window``, by start."""
    w0, w1 = window
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if keep(e.name):
                    a = int(e.start_ns)
                    b = a + int(e.duration_ns)
                    if b > w0 and a < w1:
                        out.append((e.name, a, b))
    return sorted(out, key=lambda s: s[1])


def program_spans(pd, window) -> list[tuple[str, int, int]]:
    """The ``repro.*`` host spans over ``window``, by start."""
    return host_events(pd, window, lambda n: n.startswith(PREFIX))


def device_modules(pd, plane: str) -> list[tuple[str, int, int]]:
    """(name, start ns, end ns) of the programs run on a device plane (its
    ``XLA Modules`` line), by start."""
    return sorted(((e.name, int(e.start_ns),
                    int(e.start_ns + e.duration_ns))
                   for p in pd.planes if p.name == plane
                   for line in p.lines if line.name == MODULES
                   for e in line.events), key=lambda m: m[1])


def device_lead(spans, runtime, modules) -> tuple[int | None, int | None]:
    """Bounds (ns) on how far the device's events lead the host's on the
    trace's clock.  A decode program cannot start on the chip before the
    runtime's first ``Execute`` inside its launch span:
    ``lead >= execute - start``, with the start of the nearest run of the
    program that holds the device longest (the decode step).  Nor can the
    last program of a decode call end after the runtime's last ``Done``
    inside its readback span: ``lead <= done - end``, with the device's
    last program end before it.  ``None`` where no call gives a bound."""
    held = collections.Counter()
    for name, a, b in modules:
        held[name] += b - a
    step = held.most_common(1)[0][0] if held else None
    execs = [a for n, a, _ in runtime if n == EXECUTE]
    dones = [a for n, a, _ in runtime if n == DONE]
    starts = [a for n, a, _ in modules if n == step]
    ends = sorted(b for _, _, b in modules)
    lo = hi = None
    for name, a, b in spans:
        if name == LAUNCH and starts:
            i = bisect.bisect_left(execs, a)
            if i < len(execs) and execs[i] < b:
                e = execs[i]
                j = bisect.bisect_left(starts, e)
                m = min(starts[max(j - 1, 0):j + 1], key=lambda t: abs(e - t))
                lo = e - m if lo is None else max(lo, e - m)
        elif name == READBACK:
            i = bisect.bisect_left(dones, b) - 1
            if i >= 0 and dones[i] >= a:
                j = bisect.bisect_right(ends, dones[i]) - 1
                if j >= 0:
                    d = dones[i] - ends[j]
                    hi = d if hi is None else min(hi, d)
    return lo, hi


def shifted(red: tracing.Reduced, lead: int) -> tracing.Reduced:
    """``red`` with every device operation ``lead`` ns later, clipped to
    the window (so up to ``lead`` of the window's first and last device
    time is lost to the edges)."""
    w0, w1 = red.window
    return tracing.Reduced(red.window, {
        k: [tracing.Op(o.name, o.label, o.opcode, max(o.start + lead, w0),
                       min(o.end + lead, w1))
            for o in ops if o.end + lead > w0 and o.start + lead < w1]
        for k, ops in red.devices.items()}, list(red.host))


def idle_intervals(red: tracing.Reduced) -> list[tuple[int, int]]:
    """(start ns, end ns) of the first device's idle gaps, as
    ``Reduced.idle_gaps`` finds them."""
    w0 = red.window[0]
    out = []
    for _, start, length in red.idle_gaps():
        a = w0 + round(start * 1e9)
        out.append((a, a + round(length * 1e9)))
    return out


def _segments(spans) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces of the time that some span
    covers, each named by the shortest span covering it."""
    cuts = sorted({t for _, a, b in spans for t in (a, b)})
    out, active, j = [], [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][1] <= lo:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[2] > lo]
        if active:
            name = min(active, key=lambda s: s[2] - s[1])[0]
            out.append((lo, hi, name))
    return out


def idle_pieces(red: tracing.Reduced, spans):
    """(start ns, end ns, name) of the first device's idle cut at the
    program spans' boundaries: ``name`` is the innermost span over the
    piece, or ``None`` where no program span covers it."""
    segs = _segments(sorted(spans, key=lambda s: s[1]))
    starts = [a for a, _, _ in segs]
    for a, b in idle_intervals(red):
        t = a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
            if hi > lo:
                if lo > t:
                    yield t, lo, None
                yield lo, hi, segs[i][2]
                t = hi
            i += 1
        if b > t:
            yield t, b, None


def idle_by_program_span(red: tracing.Reduced,
                         spans) -> dict[str, float]:
    """Seconds of the first device's idle, keyed by the innermost program
    span over each piece, or :data:`OUTSIDE`.  Sums to the whole idle."""
    out = collections.Counter()
    for a, b, name in idle_pieces(red, spans):
        out[name or OUTSIDE] += b - a
    return {k: v * 1e-9 for k, v in out.items()}


def starts_in_window(spans, name: str, window) -> int:
    """How many spans named ``name`` start inside ``window``."""
    w0, w1 = window
    return sum(1 for n, a, _ in spans if n == name and w0 <= a < w1)


def per_decode_call_ms(red: tracing.Reduced, spans) -> dict:
    """Each of :data:`READINGS`, and the idle outside program spans: ms of
    idle per decode call in the window; ``None`` without decode calls."""
    calls = starts_in_window(spans, LAUNCH, red.window)
    names = [*READINGS, OUTSIDE]
    if not calls:
        return dict.fromkeys(names)
    idle = idle_by_program_span(red, spans)
    out = {m: 1e3 * sum(v for k, v in idle.items() if k.startswith(pre))
           / calls for m, pre in READINGS.items()}
    out[OUTSIDE] = 1e3 * idle.get(OUTSIDE, 0.0) / calls
    return out


class Tracer(tracing.Tracer):
    """The benchmark's tracer, also keeping, when it reduces the trace,
    the program's spans of the window (``program``), the runtime's
    ``Execute`` and ``Done`` events (``runtime``) and the first device's
    programs (``modules``)."""

    def __init__(self, path: str):
        super().__init__(path)
        self.program: list[tuple[str, int, int]] = []
        self.runtime: list[tuple[str, int, int]] = []
        self.modules: list[tuple[str, int, int]] = []

    def reduce(self) -> tracing.Reduced:
        files = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no .xplane.pb under {self.path}")
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(files[0])
        red = tracing.reduce_profile(pd)
        self.program = program_spans(pd, red.window)
        self.runtime = host_events(pd, red.window,
                                   {EXECUTE, DONE}.__contains__)
        if red.devices:
            self.modules = device_modules(pd, sorted(red.devices)[0])
        shutil.rmtree(self.path, ignore_errors=True)
        return red


def measure(cell, seed: int, seconds: float, devs) -> dict:
    """Serve one traced window of a serving cell; the idle split."""
    from bench import run, serve_cell

    tracer = Tracer(os.path.join(ROOT, "bench_out", "trace",
                                 cell.name + ".program"))
    serve_cell.run(cell, seed, seconds, run.make_mesh(cell, devs),
                   tracer=tracer)
    red = tracer.reduce()
    idle = idle_by_program_span(red, tracer.program)
    # the idle outside program spans, named by the benchmark's span
    outside = collections.Counter()
    for a, b, name in idle_pieces(red, tracer.program):
        if name is None:
            outside[red.host_at((a + b) // 2)] += (b - a) * 1e-9
    host = collections.Counter()
    w0, w1 = red.window
    for name, a, b in tracer.program:
        host[name] += (min(b, w1) - max(a, w0)) * 1e-9
    return {"window_s": red.window_s, "busy_s": red.busy_s(),
            "idle_s": sum(idle.values()),
            "decode_calls": starts_in_window(tracer.program, LAUNCH,
                                             red.window),
            "idle_by_program_span_s": dict(sorted(idle.items())),
            "host_s_by_program_span": dict(sorted(host.items())),
            "per_decode_call_ms": per_decode_call_ms(red, tracer.program),
            "outside_by_bench_span_s": dict(outside.most_common()),
            "idle_gaps": red.breakdown()["idle_gaps"],
            **aligned(red, tracer)}


def aligned(red: tracing.Reduced, tracer: Tracer) -> dict:
    """The bounds on the device's lead, and the readings per decode call
    with the device's events moved later by each bound."""
    lo, hi = device_lead(tracer.program, tracer.runtime, tracer.modules)
    out = {"device_lead_ms": [None if x is None else x * 1e-6
                              for x in (lo, hi)]}
    for key, lead in (("at_lead_lo", lo), ("at_lead_hi", hi)):
        if lead is not None:
            out["per_decode_call_ms_" + key] = per_decode_call_ms(
                shifted(red, lead), tracer.program)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.find_cell(args.workload)
    if cell.traffic["kind"] != "serve":
        print(f"{args.workload} is no serving cell", file=sys.stderr)
        return 2
    try:
        devs = harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench/program_spans.py: {e}; no result", file=sys.stderr)
        return 2
    harness.set_compile_cache(ROOT)
    got = measure(cell, args.seed, args.seconds, devs)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "device": harness.device_info(devs), **got}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
