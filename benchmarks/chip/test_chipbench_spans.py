"""CPU checks of the reading of the serve loop's host spans: the device's
idle time put down to the innermost span over it, and the host's work per
decode step."""
import jax
import pytest

from chipbench import spans, trace

# Times in us from the line's timestamp.  Device: two decode programs,
# 2-8 and 12-18.  Host (the line holding serve.window, 0-20): iteration 1
# (1-10) dispatches 1-2.5, waits 2.5-9, emits 9-10 with a collection
# 9.5-10; iteration 2 (10.5-19.5) dispatches 11-12.5, waits 12.5-19,
# emits 19-19.5.  The line also holds a Python-tracer event and a runtime
# event, and a second host line holds no window.
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 6000000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 6000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name:
    "%psi_matmul_int8.7 = bf16[8,64]{1,0} custom-call(bf16[8,32] %x)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__decode_fn_paged(12)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 5 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000
             stats { metadata_id: 1 int64_value: 2 } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 9000000
             stats { metadata_id: 2 int64_value: 1 } }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 1500000
             stats { metadata_id: 3 int64_value: 2 } }
    events { metadata_id: 10 offset_ps: 1200000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 2500000 duration_ps: 6500000 }
    events { metadata_id: 5 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 9500000 duration_ps: 500000
             stats { metadata_id: 4 int64_value: 0 } }
    events { metadata_id: 2 offset_ps: 10500000 duration_ps: 9000000
             stats { metadata_id: 2 int64_value: 2 } }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 1500000
             stats { metadata_id: 3 int64_value: 2 } }
    events { metadata_id: 11 offset_ps: 11000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 12500000 duration_ps: 6500000 }
    events { metadata_id: 5 offset_ps: 19000000 duration_ps: 500000 }
  }
  lines {
    id: 6 name: "other thread" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "serve.window" } }
  event_metadata { key: 2 value { id: 2 name: "serve.iter" } }
  event_metadata { key: 3 value { id: 3 name: "decode.dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "decode.wait" } }
  event_metadata { key: 5 value { id: 5 name: "decode.emit" } }
  event_metadata { key: 6 value { id: 6 name: "gc" } }
  event_metadata { key: 10 value { id: 10 name: "$serve.py:1 _serve" } }
  event_metadata { key: 11 value { id: 11 name:
    "PjitFunction(_decode_fn_paged)" } }
  stat_metadata { key: 1 value { id: 1 name: "n_requests" } }
  stat_metadata { key: 2 value { id: 2 name: "step_num" } }
  stat_metadata { key: 3 value { id: 3 name: "n_active" } }
  stat_metadata { key: 4 value { id: 4 name: "generation" } }
}
'''


@pytest.fixture(scope="module")
def profile():
    return jax.profiler.ProfileData.from_text_proto(XSPACE)


def test_host_spans_are_the_programs_on_the_windows_line(profile):
    got = spans.host_spans(profile)
    assert [s.name for s in got] == [
        "serve.window", "serve.iter", "decode.dispatch", "decode.wait",
        "decode.emit", "gc", "serve.iter", "decode.dispatch", "decode.wait",
        "decode.emit"]
    assert got[0].args == {"n_requests": 2}
    assert [s.args["step_num"] for s in got if s.name == "serve.iter"] \
        == [1, 2]
    assert got[5].args == {"generation": 0}
    assert got[3].start_ns == pytest.approx(3500)
    assert got[3].end_ns == pytest.approx(10000)


def test_a_trace_without_a_window_has_no_spans():
    device_only = XSPACE.split("planes {\n  id: 2")[0]
    assert spans.host_spans(
        jax.profiler.ProfileData.from_text_proto(device_only)) == []
    assert spans.idle_by_host_span([], []) == []
    assert spans.host_ms_per_step([], 5) is None


def test_idle_gaps_split_among_the_innermost_spans(profile):
    """Idle 0-2, 8-12 and 18-20 us: 0-1 before the first iteration and
    10-10.5 between two are no span's; 10.5-11 is the iteration's own;
    the collection 9.5-10 takes its share from the emit around it."""
    devs = trace.devices_from_profile(profile)
    got = dict(spans.idle_by_host_span(devs, spans.host_spans(profile)))
    assert got == {
        "decode.dispatch": pytest.approx(2e-6),
        "decode.wait": pytest.approx(2e-6),
        spans.NO_SPAN: pytest.approx(2e-6),
        "decode.emit": pytest.approx(1e-6),
        "gc": pytest.approx(0.5e-6),
        "serve.iter": pytest.approx(0.5e-6)}
    assert sum(got.values()) == pytest.approx(
        20e-6 - trace.reduce(devs, 20e-6).busy_s)
    assert len(spans.idle_by_host_span(devs, spans.host_spans(profile),
                                       n=2)) == 2


def test_host_ms_per_step_leaves_the_waits_out(profile):
    """Each iteration lasts 9 us and waits 6.5: 5 us over two steps."""
    assert spans.host_ms_per_step(spans.host_spans(profile), 2) == \
        pytest.approx(2.5e-3)
    assert spans.host_ms_per_step(spans.host_spans(profile), 0) is None
