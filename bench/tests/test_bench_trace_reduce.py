"""The trace reduction: busy time as a union of operation intervals,
clipped to the harness's window; idle gaps named by the annotation the
host was in; and the same on a trace recorded on a TPU v5e."""
import os

import pytest

import smoke_root
import trace_reduce

FIXTURE = os.path.join(smoke_root.BENCH, "tests", "fixtures",
                       "v5e_tiny.xplane.pb")


def test_union_gaps_and_names():
    ms = 1_000_000
    host = [("engine.step", 0, 10 * ms, {}), ("harvest", 10 * ms, 14 * ms, {}),
            ("engine.step", 14 * ms, 20 * ms, {})]
    ops = [("fusion.1", 1 * ms, 3 * ms, {}), ("fusion.1", 3 * ms, 6 * ms, {}),
           ("paged_decode", 7 * ms, 9 * ms, {}),
           ("fusion.2", 15 * ms, 19 * ms, {}),
           ("outside", 25 * ms, 30 * ms, {})]
    red = trace_reduce.reduce_events({"/device:TPU:0": ops}, host)
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx(0.011)      # 5 + 2 + 4 ms
    names = dict((n, s) for n, s in red["device_ops"])
    assert names["fusion.1"] == pytest.approx(0.005)
    assert "outside" not in names
    gaps = red["idle_gaps"]
    assert gaps[0] == ["harvest", pytest.approx(0.006)]   # 9..15 ms
    assert sum(s for _, s in gaps) == pytest.approx(0.009)
    assert trace_reduce.kernel_seconds(red, "paged_decode") == \
        pytest.approx(0.002)
    assert trace_reduce.kernel_seconds(red, "no_such_kernel") is None


def test_nested_events_count_their_self_time():
    host = [("engine.step", 0, 100, {})]
    ops = [("%while.1", 10, 90, {}), ("%fusion.3", 20, 30, {}),
           ("%paged_flash_decode.5", 40, 80, {}), ("%fusion.3", 50, 60, {})]
    red = trace_reduce.reduce_events({"/device:TPU:0": ops}, host)
    t = dict(red["device_ops"])
    assert t["%while.1"] == pytest.approx(30e-9)
    assert t["%paged_flash_decode.5"] == pytest.approx(30e-9)
    assert t["%fusion.3"] == pytest.approx(20e-9)
    assert red["busy_s"] == pytest.approx(80e-9)
    assert trace_reduce.op_name("%fusion.1 = bf16[2]{0} fusion(x)") == \
        "%fusion.1"


def test_busy_is_averaged_over_chips():
    host = [("engine.step", 0, 100, {})]
    red = trace_reduce.reduce_events(
        {"/device:TPU:0": [("a", 0, 100, {})],
         "/device:TPU:1": [("a", 0, 50, {})]}, host)
    assert red["busy_s"] == pytest.approx(75e-9)


def test_no_annotation_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": []}, [])
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({}, [("engine.step", 0, 1, {})])


def test_recorded_chip_trace():
    red = trace_reduce.reduce(FIXTURE)
    assert 0 < red["busy_s"] < red["window_s"] < 5
    assert red["device_ops"] and red["idle_gaps"]
    assert {n for n, _ in red["idle_gaps"]} <= \
        set(trace_reduce.ANNOTATIONS) | {"host:outside harness calls"}
