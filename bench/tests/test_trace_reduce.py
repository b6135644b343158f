"""The reduction from a trace to busy time, idle share, exposed
collectives and labelled gaps, on small hand-made traces."""

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace


def make(devices, host=()):
    return Trace(window=(0, 1000), host=list(host),
                 devices=[sorted(d, key=lambda e: e.start) for d in devices])


def test_union_subtract_length():
    u = tr.union([(5, 10), (0, 3), (2, 6), (20, 25), (25, 30)])
    assert u == [(0, 10), (20, 30)]
    assert tr.length(u) == 20
    assert tr.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) == [
        (0, 10), (30, 90)]
    assert tr.clip([(-5, 5), (995, 1005), (2000, 3000)], 0, 1000) == [
        (0, 5), (995, 1000)]


def test_busy_and_idle_share_average_over_devices():
    d0 = [Event("fusion.1", 0, 300), Event("fusion.2", 200, 500)]
    d1 = [Event("fusion.1", -100, 100), Event("fusion.3", 900, 1200)]
    t = make([d0, d1])
    # device 0 busy 500 ns, device 1 busy 100 + 100 ns inside the window
    assert t.window_ns == 1000
    assert tr.busy_s(t) == pytest.approx((500 + 200) / 2 / 1e9)
    assert tr.idle_share(t) == pytest.approx(1 - 350 / 1000)


KERNEL = ('%closed_call.24 = bf16[24,32,128]{2,1,0:T(8,128)(2,1)} '
          'custom-call(s32[24,128]{1,0} %a), '
          'custom_call_target="tpu_custom_call"')


def test_pallas_kernel_time_by_its_output_type():
    d0 = [Event(KERNEL, 0, 100),
          Event(KERNEL.replace("closed_call.24", "closed_call.25"), 200, 250),
          Event("%fusion.1 = bf16[24,32,128]{2,1,0} fusion(%x)", 300, 400),
          Event(KERNEL.replace("24,32", "8,32"), 500, 600),
          Event(KERNEL, 950, 1100)]
    t = make([d0])
    assert tr.kernel_s(t, "bf16[24,32,128]") == pytest.approx(200 / 1e9)
    assert len(tr.kernel_events(t, "bf16[24,32,128]")) == 3
    assert tr.op_key(d0[0]) == "tpu_custom_call bf16[24,32,128]"
    assert tr.op_key(d0[2]) == "fusion bf16[24,32,128]"


def test_exposed_collective_time():
    d0 = [Event("%collective-permute-start.1 = (f32[8,16]) "
                "collective-permute-start(%x)", 0, 100),
          Event("%fusion.1 = f32[8,16]{1,0} fusion(%collective-permute-done"
                ".1)", 50, 150),
          Event("%all-reduce.2 = f32[] all-reduce(%y)", 400, 500),
          Event("fusion.2", 450, 460)]
    t = make([d0])
    total, exposed = tr.exposed_collective_s(t, 0)
    assert total == pytest.approx(200 / 1e9)
    # permute hidden after 50 ns; all-reduce hidden for 10 of its 100 ns
    assert exposed == pytest.approx((50 + 90) / 1e9)


def test_gap_labels_take_the_innermost_harness_span():
    d0 = [Event("fusion.1", 0, 100), Event("fusion.2", 300, 400),
          Event("fusion.3", 700, 1000)]
    host = [Event("bench.serve.run", 0, 1000),
            Event("bench.serve.dispatch", 120, 280)]
    t = make([d0], host)
    gaps = tr.idle_gaps(t, 0)
    assert gaps == [(100, 300), (400, 700)]
    assert tr.label(t, 200) == "bench.serve.dispatch"
    assert tr.label(t, 550) == "bench.serve.run"
    assert tr.label(t, 5000) == "none"
    b = tr.breakdown(t)
    assert b["idle_gaps"] == [["bench.serve.run", 300 / 1e9],
                              ["bench.serve.dispatch", 200 / 1e9]]
    assert b["device_ops"][0] == ["fusion", 500 / 1e9]
    assert tr.op_key(Event("%while.21 = (s32[], f32[8]) while(%t)", 0, 1)) \
        == "while (s32[]"


XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.halo.readback" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 400000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.3" } }
  event_metadata { key: 2 value { id: 2 name: "%closed_call.7 = f32[16,256]{1,0} custom-call(%u), custom_call_target=\\"tpu_custom_call\\"" } }
}
"""


def test_loads_a_recorded_xspace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    t = tr.from_profile(pd)
    assert t.window == (1000, 2000)
    assert [e.name for e in t.host] == ["bench.halo.readback"]
    assert len(t.devices) == 1
    assert tr.kernel_s(t, "f32[16,256]") == pytest.approx(500 / 1e9)
    assert tr.idle_share(t) == pytest.approx(0.4)
    assert tr.label(t, 1100 + 150) == "bench.halo.readback"
