"""The trace-to-metrics reduction on small synthetic traces."""
import types

import pytest

import harness
import metric_lib
import reduce_trace as rt

DEV = "/device:TPU:0"
HOST = rt.HOST_PLANE


BP = ('%backproject_pallas.2 = f32[32,2048,2048]{2,1,0} custom-call('
      'f32[3072]{0} %get-tuple-element.11), '
      'custom_call_target="tpu_custom_call"')
#: an operation that reads the kernel's output: not the kernel
AFTER_BP = ('%multiply_divide_fusion = f32[32,2048,2048]{2,1,0} fusion('
            'f32[32,2048,2048]{2,1,0} %backproject_pallas.2), kind=kLoop')


def op(start, dur, name="%fusion.3 = f32[8]{0} fusion()", plane=DEV):
    return rt.Event(plane, rt.OPS_LINE, name, start, dur)


def span(name, start, end):
    return rt.Event(HOST, "python", name, start, end - start)


def test_union_and_gaps():
    assert rt.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert rt.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert rt.gaps([], 2, 4) == [(2, 4)]
    assert rt.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def test_op_names():
    assert rt.Event(DEV, rt.OPS_LINE, BP, 0, 1).op == "backproject_pallas"
    assert rt.Event(DEV, rt.OPS_LINE, BP, 0, 1).is_kernel(
        "backproject_pallas")
    after = rt.Event(DEV, rt.OPS_LINE, AFTER_BP, 0, 1)
    assert after.op == "multiply_divide_fusion"
    assert not after.is_kernel("backproject_pallas")


def test_busy_idle_and_kernel_time():
    events = [span(rt.WINDOW_SPAN, 0, 100),
              op(-10, 20),                      # clipped to [0, 10]
              op(5, 10),                        # overlaps the first
              op(40, 30, BP),
              op(95, 20, BP),                   # clipped to [95, 100]
              op(70, 1, AFTER_BP),
              span("client.wait", 10, 60),
              span("client.result", 60, 100)]
    lo, hi = rt.window(events)
    assert (lo, hi) == (0, 100)
    assert rt.busy_ns(events, lo, hi) == 15 + 31 + 5
    assert rt.op_seconds(events, lo, hi, "backproject_pallas") == \
        pytest.approx(35e-9)
    assert rt.kernel_calls(events, lo, hi, "backproject_pallas") == 1
    assert rt.top_ops(events, lo, hi)[0] == ["backproject_pallas",
                                             pytest.approx(35e-9)]
    idle = dict(rt.idle_by_host_span(events, lo, hi))
    # idle: (15, 40) under wait, (71, 95) under result
    assert idle == {"client.wait": pytest.approx(25e-9),
                    "client.result": pytest.approx(24e-9)}


def test_two_devices_average():
    events = [span(rt.WINDOW_SPAN, 0, 100), op(0, 50),
              op(0, 100, plane="/device:TPU:1")]
    assert rt.busy_ns(events, 0, 100) == 75


def test_no_device_ops_is_an_error():
    with pytest.raises(RuntimeError):
        rt.busy_ns([span(rt.WINDOW_SPAN, 0, 1)], 0, 1)


def test_load_events_from_xspace(tmp_path):
    from jax.profiler import ProfileData
    text = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%backproject_pallas.2 = f32[4]{0} custom-call(), custom_call_target=\\"tpu_custom_call\\"" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
planes { id: 3 name: "/host:metadata" }
'''
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(raw)
    events = rt.load_events(rt.find_xspace(str(tmp_path)))
    assert {e.plane for e in events} == {"/device:TPU:0", HOST}
    assert rt.window(events) == (1000, 11000)
    assert rt.op_seconds(events, 1000, 11000, "backproject_pallas") == \
        pytest.approx(5e-6)


def _run(events, n_done=2, rows=32):
    config = {"process_list": {"plugins": [
        {"plugin": "synthetic_tomo_loader",
         "params": {"n_angles": 3072, "n_rows": rows, "n_det": 2048}}]}}
    req = types.SimpleNamespace(error=None, latency_s=1.0, result_s=0.5)
    return harness.Run(
        cell={}, config=config, traffic={}, setup_s=1.0, t0=0.0, t1=10.0,
        requests=[req] * n_done, compiles_in_window=0,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, device={},
        events=events, trace_window=rt.window(events))


def test_readers_on_a_synthetic_trace():
    # 2 scans of 32 slices; the kernel runs 1 s per slice
    events = [span(rt.WINDOW_SPAN, 0, 100e9)] + [
        op(i * 1.5e9, 1e9, BP) for i in range(64)]
    run = _run(events)
    share = harness.reader("backproject_roofline.scan")(run)
    flops = 4 * 2048 * 2048 * 3072 * 64
    assert share == pytest.approx(100 * flops / 197e12 / 64.0)
    assert 0 < share < 100
    assert harness.reader("device_idle_share.scan")(run) == \
        pytest.approx(36.0)
    assert harness.reader("xla_steps_device_s.scan")(run) == 0.0
    # no sinogram-filter kernel in this trace: nothing to read
    assert harness.reader("sino_filter_roofline.scan")(run) is None


def test_readers_without_a_trace_read_nothing():
    run = _run([span(rt.WINDOW_SPAN, 0, 1), op(0, 1)])
    run.events = None
    for name in ("device_idle_share.scan", "backproject_roofline.scan",
                 "sino_filter_roofline.scan", "xla_steps_device_s.scan"):
        assert harness.reader(name)(run) is None


def test_every_metric_has_a_reader():
    bench = harness.load_benchmark()
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(harness.reader(m["name"])), m["name"]
    assert metric_lib.percentile([], 90) is None
