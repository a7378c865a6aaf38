"""The device idle split by the program's ``repro.*`` spans, on a small
synthetic trace of served steps, and on a real profile of a tiny serving
cell on the CPU."""
import types

import pytest

from bench import harness, program_spans, tracing
from bench.tests import test_bench_trace as T, tiny

FLASH, FUSION, REDUCE = T.FLASH, T.FUSION, T.REDUCE

# us; the window is 10-210.  The last span is a training step's, so that
# every reader of the benchmark's has something to read.
BENCH = [("bench.window", 10, 210), ("bench.prefill.512", 32, 66),
         ("bench.decode", 66, 120), ("bench.decode", 131, 200),
         ("bench.step", 206, 215)]
PROGRAM = [
    # the tail of the decode call before the window
    ("repro.decode.launch", 4, 12), ("repro.decode.sample", 12, 13),
    ("repro.decode.readback", 13, 20), ("repro.sched.retire", 20, 24),
    # a step that prefills and decodes
    ("repro.sched.admit", 24, 28), ("repro.sched.price", 28, 32),
    ("repro.decode.inputs", 67, 72), ("repro.decode.launch", 72, 78),
    ("repro.decode.sample", 78, 80), ("repro.decode.readback", 80, 118),
    ("repro.sched.retire", 121, 125),
    # a step that decodes
    ("repro.sched.admit", 125, 128), ("repro.sched.price", 128, 130),
    ("repro.decode.inputs", 132, 137), ("repro.decode.launch", 137, 145),
    ("repro.decode.sample", 145, 147), ("repro.decode.readback", 147, 198),
    ("repro.sched.retire", 200, 203),
    # a step whose decode call outlasts the window
    ("repro.sched.admit", 203, 204), ("repro.sched.price", 204, 206),
    ("repro.decode.inputs", 207, 209), ("repro.decode.launch", 209, 212)]


def served(program: bool) -> str:
    """Device 0 runs a decode program 0-11, the prefill's flash kernel
    40-60, decode programs 75-100, 140-180 and 205-230; it idles 11-40,
    60-75, 100-140 and 180-205 of the window.  Device 1 computes 20-50
    and reduces 50-90."""
    dev0 = T._plane(1, "/device:TPU:0", "XLA Ops", [
        (1, 0, 11, []), (2, 40, 20, []), (1, 75, 25, []), (1, 140, 40, []),
        (1, 205, 25, [])], {1: FUSION, 2: FLASH})
    dev1 = T._plane(2, "/device:TPU:1", "XLA Ops", [
        (1, 20, 30, []), (2, 50, 40, [])], {1: FUSION, 2: REDUCE})
    spans = BENCH + (PROGRAM if program else [])
    names = {n: i + 1 for i, n in enumerate(dict.fromkeys(
        n for n, _, _ in spans))}
    host = T._plane(3, "/host:CPU", "python",
                    [(names[n], a, b - a, []) for n, a, b in spans],
                    {i: n for n, i in names.items()})
    return "\n".join([dev0, dev1, host])


def _profile(program: bool):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(served(program))


@pytest.fixture(scope="module")
def traced():
    pd = _profile(True)
    red = tracing.reduce_profile(pd)
    return red, program_spans.program_spans(pd, red.window)


def test_program_spans_are_those_over_the_window(traced):
    red, spans = traced
    assert [(n, a // 1000, b // 1000) for n, a, b in spans] == PROGRAM
    assert [n for n, _, _ in red.host] == [n for n, _, _ in BENCH[1:]]


def test_an_idle_gap_is_cut_at_every_program_span_boundary(traced):
    red, spans = traced
    pieces = [(a // 1000, b // 1000, n)
              for a, b, n in program_spans.idle_pieces(red, spans)
              if 100_000 <= a < 140_000]
    assert pieces == [
        (100, 118, "repro.decode.readback"), (118, 121, None),
        (121, 125, "repro.sched.retire"), (125, 128, "repro.sched.admit"),
        (128, 130, "repro.sched.price"), (130, 132, None),
        (132, 137, "repro.decode.inputs"), (137, 140, "repro.decode.launch")]


def test_a_piece_goes_to_the_innermost_span():
    op = lambda a, b: tracing.Op("f", "f (fusion)", "fusion", a, b)
    red = tracing.Reduced((0, 100), {"/device:TPU:0": [op(0, 10),
                                                       op(50, 100)]}, [])
    spans = [("repro.outer", 5, 60), ("repro.inner", 20, 40)]
    assert list(program_spans.idle_pieces(red, spans)) == [
        (10, 20, "repro.outer"), (20, 40, "repro.inner"),
        (40, 50, "repro.outer")]


def test_idle_by_program_span_sums_to_the_idle(traced):
    red, spans = traced
    idle = program_spans.idle_by_program_span(red, spans)
    us = {k: round(v * 1e6, 6) for k, v in idle.items()}
    assert us == {"repro.decode.launch": 7, "repro.decode.sample": 1,
                  "repro.decode.readback": 43, "repro.decode.inputs": 10,
                  "repro.sched.retire": 11, "repro.sched.admit": 8,
                  "repro.sched.price": 7, program_spans.OUTSIDE: 22}
    gaps = sum(n for _, _, n in red.idle_gaps())
    assert sum(idle.values()) == pytest.approx(gaps) == pytest.approx(109e-6)


def test_readings_per_decode_call(traced):
    red, spans = traced
    # three decode launches start in the window; the one at 4 us does not
    assert program_spans.starts_in_window(
        spans, program_spans.LAUNCH, red.window) == 3
    got = program_spans.per_decode_call_ms(red, spans)
    assert got == pytest.approx({
        "serve.decode_launch_idle_ms": 17e-3 / 3,
        "serve.decode_readback_idle_ms": 44e-3 / 3,
        "serve.sched_idle_ms": 26e-3 / 3,
        program_spans.OUTSIDE: 22e-3 / 3})
    assert sum(got.values()) * 3 == pytest.approx(109e-3)


def test_no_decode_call_reads_none():
    red = tracing.reduce_profile(_profile(False))
    assert program_spans.per_decode_call_ms(red, []) == dict.fromkeys(
        [*program_spans.READINGS, program_spans.OUTSIDE])


@pytest.mark.parametrize(
    "m", [m for m in harness.benchmark_spec()["per_layer"]
          if m["source"] == "device_trace"], ids=lambda m: m["name"])
def test_the_benchmarks_readers_do_not_see_program_spans(m):
    cell = harness.find_cell(m["workloads"][0])
    reds = [tracing.reduce_profile(_profile(p)) for p in (False, True)]
    got = [harness.metric_reader(m["name"])(types.SimpleNamespace(
        kind=cell.traffic["kind"], trace=r, config=cell.config,
        traffic=cell.traffic, peak=harness.peaks("TPU v5 lite"),
        chips=cell.chips)) for r in reds]
    assert got[0] is not None and got[0] == got[1]
    plain, spanned = reds
    assert spanned.host == plain.host
    assert spanned.idle_gaps() == plain.idle_gaps()
    assert spanned.breakdown() == plain.breakdown()
    assert spanned.exposed_collective_s() == plain.exposed_collective_s()


def test_a_tiny_served_window_on_the_cpu(tmp_path):
    """The tracer keeps the program's spans of a real profile: one
    decode launch for each decode call made while the window was open."""
    import jax

    from bench import run, serve_cell
    cell = tiny.cell("serve")
    tracer = program_spans.Tracer(str(tmp_path / "trace"))
    out = serve_cell.run(cell, 2 ** 33 + 5, 1.0,
                         run.make_mesh(cell, jax.devices()[:1]),
                         tracer=tracer)
    red = tracer.reduce()
    tx = out["tx"]
    decodes = sum(1 for c in tx.calls if c.kind == "decode")
    assert program_spans.starts_in_window(
        tracer.program, program_spans.LAUNCH, red.window) == \
        decodes - tx.warmup_steps > 0
    assert {n for n, _, _ in tracer.program} == {
        "repro.decode.inputs", "repro.decode.launch", "repro.decode.sample",
        "repro.decode.readback", "repro.sched.admit", "repro.sched.price",
        "repro.sched.retire"}
    assert not (tmp_path / "trace").exists()


def test_the_devices_lead_is_bounded_by_the_runtimes_events():
    """The device leads the host by 1,000 ns on the trace.  Call one: the
    runtime launches at 1,300 and the chip starts at 1,350 (350 on the
    trace); its last program ends at 13,020 (12,020), seen done at 13,100.
    Call two: launched at 16,300, started at 16,320; ends at 28,020, seen
    done at 28,050."""
    spans = [("repro.decode.launch", 1000, 1400),
             ("repro.decode.readback", 1500, 14000),
             ("repro.decode.launch", 16000, 16400),
             ("repro.decode.readback", 16500, 29000)]
    runtime = [(program_spans.EXECUTE, 1300, 1350),
               (program_spans.DONE, 13050, 13060),
               (program_spans.DONE, 13100, 13110),
               (program_spans.EXECUTE, 16300, 16330),
               (program_spans.DONE, 28050, 28060)]
    modules = [("step", 350, 12000), ("slice", 12005, 12010),
               ("argmax", 12010, 12020), ("step", 15320, 27000),
               ("slice", 27005, 27010), ("argmax", 27010, 27020)]
    assert program_spans.device_lead(spans, runtime, modules) == (980, 1030)
    assert program_spans.device_lead(spans, [], modules) == (None, None)
    # had the chip been busy with another program (13,800 to 19,250 on
    # the trace) when call two was launched, its decode step would start
    # late, at 19,300; the other program does not pass for the step
    busy = modules[:3] + [("scatter", 13800, 19250), ("step", 19300, 31000),
                          ("argmax", 31010, 31020)]
    late = spans[:3] + [("repro.decode.readback", 16500, 33000)]
    assert program_spans.device_lead(
        late, runtime[:-1] + [(program_spans.DONE, 32060, 32070)],
        busy) == (950, 1040)


def test_shifting_the_device_moves_its_idle(traced):
    red, spans = traced
    moved = program_spans.shifted(red, 5_000)
    gaps = [(round(a * 1e6), round(n * 1e6))
            for _, a, n in moved.idle_gaps()]
    # us from the window's start at 10: the reduction had cut device 0's
    # first program to 10-11, so it now runs 15-16, then 45-65, 80-105 and
    # 145-185; its last one, cut to 205-210, moves out of the window
    assert gaps == [(0, 5), (6, 29), (55, 15), (95, 40), (175, 25)]
    assert moved.host == red.host and moved.window == red.window


def test_runtime_events_and_device_programs_are_read_from_the_trace():
    from jax.profiler import ProfileData
    dev = T._plane(1, "/device:TPU:0", "XLA Modules",
                   [(1, 5, 10, []), (1, 30, 5, [])], {1: "jit_step(1)"})
    host = T._plane(2, "/host:CPU", "main", [
        (1, 0, 100, []), (2, 3, 1, []), (3, 20, 1, []), (4, 25, 2, [])],
        {1: "bench.window", 2: program_spans.EXECUTE,
         3: program_spans.DONE, 4: "tpu::System::Execute=>Other"})
    pd = ProfileData.from_text_proto("\n".join([dev, host]))
    assert program_spans.device_modules(pd, "/device:TPU:0") == [
        ("jit_step(1)", 5000, 15000), ("jit_step(1)", 30000, 35000)]
    keep = {program_spans.EXECUTE, program_spans.DONE}.__contains__
    assert program_spans.host_events(pd, (0, 100_000), keep) == [
        (program_spans.EXECUTE, 3000, 4000), (program_spans.DONE, 20000,
                                               21000)]
