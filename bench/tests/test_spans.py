"""The readers of the program's own spans on hand-built traces."""
import types

import pytest

import metric_lib
import reduce_trace as rt
import spans

DEV = "/device:TPU:0"
HOST = rt.HOST_PLANE


def prog(name, start, end, line="python", span_id="", parent_id="",
         **attrs):
    """A program span as the program mirrors it: its attrs, trace id,
    span id and parent id as stats."""
    stats = (*attrs.items(), ("trace_id", "t1"), ("span_id", span_id),
             ("parent_id", parent_id))
    return rt.Event(HOST, line, name, start, end - start, tuple(stats))


def bench(name, start, end):
    return rt.Event(HOST, "python", name, start, end - start)


def op(start, dur):
    return rt.Event(DEV, rt.OPS_LINE, "%fusion.3 = f32[8]{0} fusion()",
                    start, dur)


def run_of(events, done=2):
    return types.SimpleNamespace(events=events,
                                 trace_window=rt.window(events),
                                 done=[object()] * done)


def test_program_spans_come_from_every_host_line_and_only_the_program():
    events = [bench(rt.WINDOW_SPAN, 100, 200),
              bench("client.wait", 110, 150),
              prog("job.run", 110, 140, line="pipeline-w0"),
              prog("result.fetch", 150, 190, line="http-1"),
              prog("job.run", 10, 90, line="pipeline-w0"),    # warm-up
              op(120, 10)]
    got = spans.program_spans(run_of(events))
    assert sorted((e.name, e.line) for e in got) == [
        ("job.run", "pipeline-w0"), ("result.fetch", "http-1")]
    assert spans.program_spans(types.SimpleNamespace(events=None)) == []


def test_per_request_clips_to_the_window():
    events = [bench(rt.WINDOW_SPAN, 100, 200),
              prog("transfer.h2d", 90, 110),       # 10 ns inside
              prog("transfer.h2d", 120, 130),
              prog("transfer.d2h", 195, 220),      # 5 ns inside
              prog("result.send", 140, 160)]
    run = run_of(events, done=2)
    assert spans.per_request(run, "transfer.h2d") == pytest.approx(
        20e-9 / 2)
    both = spans.per_request(
        run, lambda n: n in ("transfer.h2d", "transfer.d2h"))
    assert both == pytest.approx(25e-9 / 2)
    assert spans.per_request(run, "loader.truth") is None
    assert spans.per_request(run_of(events, done=0), "result.send") is None


def test_self_time_leaves_out_the_children():
    events = [bench(rt.WINDOW_SPAN, 0, 100),
              prog("plugin.a.process", 10, 50, span_id="a"),
              prog("transfer.h2d", 12, 22, span_id="h", parent_id="a"),
              prog("compile", 20, 30, span_id="c", parent_id="a"),
              # not a child: the same interval, another parent
              prog("result.send", 40, 50, line="http-1", span_id="s",
                   parent_id="f"),
              prog("plugin.b.process", 90, 120, span_id="b"),
              prog("transfer.d2h", 95, 110, span_id="d", parent_id="b")]
    run = run_of(events, done=1)
    process = lambda n: n.endswith(".process")  # noqa: E731
    # a: 40 - union(12-22, 20-30) = 22; b clipped to 90-100, less 95-100
    assert spans.per_request(run, process, self_time=True) == \
        pytest.approx((22 + 5) * 1e-9)
    assert spans.per_request(run, process) == pytest.approx(50e-9)


def test_stat_sum():
    events = [bench(rt.WINDOW_SPAN, 100, 200),
              prog("transfer.d2h", 50, 60, bytes=7),         # warm-up
              prog("transfer.d2h", 110, 120, bytes=402653184),
              prog("transfer.d2h", 150, 190, line="http-1",
                   bytes=536870912),
              prog("transfer.h2d", 105, 108, bytes=402653184)]
    run = run_of(events)
    assert spans.stat_sum(run, "transfer.d2h", "bytes") == 939524096
    assert spans.stat_sum(run, "transfer.h2d", "bytes") == 402653184
    assert spans.stat_sum(run, "result.send", "bytes") is None


def test_idle_by_span_names_the_innermost_program_span():
    events = [bench(rt.WINDOW_SPAN, 0, 110),
              bench("client.wait", 0, 60),
              prog("job.run", 0, 40, span_id="j"),
              prog("plugin.synthetic_tomo_loader.setup", 5, 25,
                   span_id="l", parent_id="j"),
              prog("result.fetch", 60, 100, line="http-1"),
              op(40, 30)]
    got = dict(spans.idle_by_span(run_of(events)))
    assert got == pytest.approx({
        "plugin.synthetic_tomo_loader.setup": 20e-9,
        "job.run": 20e-9,         # 0-5 and 25-40
        "result.fetch": 30e-9,    # 70-100; 60-70 the device is busy
        "none": 10e-9})


@pytest.mark.parametrize("name,want", [
    ("loader_host_s.scan", 20e-9 / 2),
    ("loader_host_s.preview", 20e-9 / 2),
    ("transfer_s.scan", 15e-9 / 2),
    ("h2d_bytes.scan", 12 / 2),
    ("d2h_bytes.scan", 30 / 2),
    ("result_send_s.scan", 8e-9 / 2),
    ("dispatch_host_s.preview", (10 - 5) * 1e-9 / 2),
    ("result_device_wait_s.preview", 4e-9 / 2),
])
def test_readers(name, want):
    events = [bench(rt.WINDOW_SPAN, 0, 100),
              prog("job.run", 0, 50, span_id="j"),
              prog("plugin.synthetic_tomo_loader.setup", 0, 20,
                   span_id="l", parent_id="j"),
              prog("transfer.d2h", 2, 7, span_id="d1", parent_id="l",
                   bytes=10),
              prog("plugin.dark_flat_correction.process", 30, 40,
                   span_id="p", parent_id="j"),
              prog("transfer.h2d", 31, 36, span_id="h", parent_id="p",
                   bytes=12),
              prog("result.fetch", 60, 80, line="http-1", span_id="f"),
              prog("result.device_wait", 60, 64, line="http-1",
                   parent_id="f"),
              prog("transfer.d2h", 64, 69, line="http-1", parent_id="f",
                   bytes=20),
              prog("result.send", 70, 78, line="http-1", parent_id="f")]
    read = metric_lib.load("metrics", name).read
    assert read(run_of(events, done=2)) == pytest.approx(want)
    untraced = types.SimpleNamespace(events=None, done=[object()])
    assert read(untraced) is None
    # a program that mirrors no span: the benchmark's own spans only
    bare = [e for e in events if spans.stat(e, "trace_id") is None]
    assert read(run_of(bare, done=2)) is None
