"""The reduction from a profiler trace to busy time, kernel time, exposed
collective time and named idle gaps, on a small synthetic trace."""
import pytest

from bench import harness, tracing

US = 1_000_000   # picoseconds in a microsecond


def _plane(pid, name, line, events, names, stats=()):
    ev = "\n".join(
        f"events {{ metadata_id: {mid} offset_ps: {a * US} "
        f"duration_ps: {d * US} "
        + "".join(f'stats {{ metadata_id: {sid} str_value: "{sv}" }} '
                  for sid, sv in st)
        + "}" for mid, a, d, st in events)
    md = "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{v}" }} }}' for k, v in names.items())
    sm = "\n".join(f'stat_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{v}" }} }}' for k, v in stats)
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {ev} }} {md} {sm} }}')


FUSION = "%fusion.1 = bf16[8]{0} fusion(%p.1), kind=kLoop"
REDUCE = "%all-reduce.3 = f32[8]{0} all-reduce(%fusion.1), to_apply=%add"
AFTER = "%fusion.2 = f32[8]{0} fusion(%all-reduce.3), kind=kLoop"
FLASH = ("%checkpoint.3 = (bf16[8,3,2048,128]{3,2,1,0}, f32[32,1,1536]{2,1,0})"
         " custom-call(%a, %b, %c), custom_call_target=\\\"tpu_custom_call\\\"")
LOOP = "%while.13 = (s32[], bf16[16]{0}) while(%tuple.1), body=%body"


def synthetic():
    """Window 0..100 us.  Device 0: a loop 5-75 around compute 10-30, an
    all-reduce 30-50 with a fusion that reads it 40-45 under it, and the
    flash kernel 60-70.  Device 1: compute 10-20 and an all-reduce-start
    20-40.  Host: a decode span 0-50, a step span 55-75; the rest is the
    host outside the benchmark's calls."""
    dev0 = _plane(1, "/device:TPU:0", "XLA Ops", [
        (5, 5, 70, []),
        (1, 10, 20, []),
        (2, 30, 20, []),
        (4, 40, 5, []),
        (3, 60, 10, []),
        (1, 120, 5, []),     # after the window
    ], {1: FUSION, 2: REDUCE, 3: FLASH, 4: AFTER, 5: LOOP})
    dev1 = _plane(2, "/device:TPU:1", "XLA Ops", [
        (1, 10, 10, []), (2, 20, 20, [])],
        {1: FUSION, 2: "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%x)"})
    host = _plane(3, "/host:CPU", "python", [
        (1, 0, 100, []), (2, 0, 50, []), (3, 55, 20, [])],
        {1: "bench.window", 2: "bench.decode", 3: "bench.step"})
    other = _plane(4, "/device:TPU:0 SparseCore", "XLA Ops",
                   [(1, 0, 90, [])], {1: "ignored"})
    return "\n".join([dev0, dev1, host, other])


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return tracing.reduce_profile(ProfileData.from_text_proto(synthetic()))


def test_window_and_clipping(reduced):
    assert reduced.window == (0, 100_000)
    assert reduced.window_s == pytest.approx(100e-6)
    assert sorted(reduced.devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert all(o.end <= 100_000 for ops in reduced.devices.values()
               for o in ops)
    assert [n for n, _, _ in reduced.host] == ["bench.decode", "bench.step"]


def test_busy_is_the_union_averaged_over_devices(reduced):
    # device 0: 10-50 and 60-70 -> 50 us; device 1: 10-40 -> 30 us
    assert reduced.busy_s() == pytest.approx(40e-6)


def test_ops_are_parsed_from_their_hlo_text(reduced):
    ops = reduced.devices["/device:TPU:0"]
    assert [(o.label, o.opcode) for o in ops] == [
        ("fusion.1 (fusion)", "fusion"),
        ("all-reduce.3 (all-reduce)", "all-reduce"),
        ("fusion.2 (fusion)", "fusion"),
        ("checkpoint.3 (custom-call)", "custom-call")]    # no while loop
    kernel = harness.metric_reader("serve.flash_fwd_roofline").__globals__[
        "KERNEL"]
    assert [o.end - o.start for o in reduced.kernel_ops(kernel)] == [10_000]


def test_exposed_collective_leaves_out_overlap(reduced):
    # device 0: 20 us of all-reduce, 5 of them under compute -> 15;
    # device 1: 20 us with nothing under it -> 20; mean 17.5 us
    assert reduced.exposed_collective_s() == pytest.approx(17.5e-6)


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = [(who, round(start * 1e6), round(n * 1e6))
            for who, start, n in reduced.idle_gaps()]
    assert gaps == [("bench.decode", 0, 10), ("bench.step", 50, 10),
                    ("host outside bench calls", 70, 30)]


def test_breakdown_lists_ops_and_idle(reduced):
    b = reduced.breakdown()
    ops = dict(b["device_ops"])
    assert ops["all-reduce.3 (all-reduce)"] == pytest.approx(10e-6)
    assert ops["fusion.1 (fusion)"] == pytest.approx(15e-6)  # 30 us over 2
    assert b["idle_gaps"][0] == ["host outside bench calls",
                                 pytest.approx(30e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    txt = _plane(1, "/device:TPU:0", "XLA Ops", [(1, 0, 5, [])],
                 {1: "fusion.1"})
    with pytest.raises(RuntimeError, match="bench.window"):
        tracing.reduce_profile(ProfileData.from_text_proto(txt))
