"""The program's own spans (``repro.obs.tracer`` spans, profiler
annotations while a trace records) beside the harness's: the trace
reduction keeps only the ``bench.*`` host spans, so every reading and
every gap label is what it was without the program's spans."""

import glob

import pytest

from bench import trace_reduce as tr
from bench.tests.test_trace_reduce import XSPACE


def test_a_recorded_trace_keeps_only_the_harness_spans(tmp_path):
    jax = pytest.importorskip("jax")
    from repro.obs.tracer import get_tracer
    span = get_tracer().span
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.serve.run"):
                with span("serve.quantum", chunk=1):
                    with span("serve.dispatch"):
                        jax.block_until_ready(jax.numpy.ones(8) * 2)
                with span("serve.complete"):
                    pass
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(
        path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events}
    # recorded, and left out of the reduction
    assert {"serve.quantum", "serve.dispatch", "serve.complete"} <= names
    assert [e.name for e in tr.load(path).host] == ["bench.serve.run"]


# a program span open over the device's idle 300-400 ns, nested in the
# harness's readback span
PROGRAM_PLANE = """
planes {
  id: 3 name: "/host:metadata"
  lines { id: 2 name: "engine" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 300000 }
    events { metadata_id: 2 offset_ps: 200000 duration_ps: 150000 } }
  event_metadata { key: 1 value { id: 1 name: "serve.quantum" } }
  event_metadata { key: 2 value { id: 2 name: "train.sync" } }
}
"""


def test_program_spans_leave_every_reading_unchanged():
    from jax.profiler import ProfileData

    def reduce(text):
        return tr.from_profile(ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(text)))

    without, with_ = reduce(XSPACE), reduce(XSPACE + PROGRAM_PLANE)
    assert with_.host == without.host
    assert tr.label(with_, 1250) == "bench.halo.readback"
    for f in (tr.busy_s, tr.idle_share, tr.idle_gaps, tr.breakdown):
        assert f(with_) == f(without)
    assert tr.kernel_s(with_, "f32[16,256]") == \
        tr.kernel_s(without, "f32[16,256]")
    assert tr.exposed_collective_s(with_) == tr.exposed_collective_s(without)
