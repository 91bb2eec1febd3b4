"""The trace reduction on hand-made op timelines and a recorded trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace as tr  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_axpy_flash.xplane.pb"


def ops(*events):
    return tr.DeviceOps(0, list(events))


def test_busy_is_the_union_of_op_intervals():
    d = ops(("a", 0, 10), ("b", 5, 15), ("c", 20, 30))
    assert tr.busy(d) == [(0, 15), (20, 30)]
    assert tr.busy_s(d) == pytest.approx(25e-9)
    assert tr.busy_s(d, (10, 25)) == pytest.approx(10e-9)
    assert tr.idle_gaps(d, (0, 40)) == [(15, 20), (30, 40)]


def test_exposed_collective_time():
    d = ops(("fusion.1", 0, 10), ("all-gather-start.2", 5, 20),
            ("all-reduce.3", 25, 30), ("fusion.4", 27, 40))
    # all-gather alone 10..20, all-reduce alone 25..27
    assert tr.exposed_collective_s(d) == pytest.approx(12e-9)


def test_kind_strips_instance_and_text():
    assert tr.kind("%seeded_axpy_pallas.12 = f32[9,128]{1,0} custom-call("
                   "%a), custom_call_target=\"tpu_custom_call\"") \
        == "seeded_axpy_pallas"
    assert tr.kind("%pad.405.clone = f32[8]{0} pad(%x)") == "pad"
    assert tr.kind("fusion") == "fusion"


def test_self_time_excludes_nested_ops():
    # a while loop whose body ran two ops
    d = ops(("%while.3 = (s32[]) while(%t)", 0, 100),
            ("%flash_attention_pallas.1 = f32[2] custom-call()", 10, 40),
            ("%fusion.2 = f32[2] fusion()", 40, 70),
            ("%seeded_axpy_pallas.4 = f32[2] custom-call()", 100, 130))
    self_ns = dict((tr.kind(n), s) for n, s in tr.self_times(d))
    assert self_ns == {"while": 40, "flash_attention_pallas": 30,
                       "fusion": 30, "seeded_axpy_pallas": 30}
    assert sum(self_ns.values()) * 1e-9 == pytest.approx(tr.busy_s(d))


def test_op_seconds_and_top_ops():
    devs = [ops(("%seeded_axpy_pallas.1 = f32[2]", 0, 4),
                ("%fusion.2 = f32[2]", 4, 5)),
            tr.DeviceOps(1, [("%seeded_axpy_pallas.7 = f32[2]", 0, 2)])]
    match = lambda k: k == "seeded_axpy_pallas"
    assert tr.op_seconds(devs, match) == pytest.approx(6e-9)
    assert tr.op_count(devs, match) == 2
    assert tr.top_ops(devs)[0] == ("seeded_axpy_pallas",
                                   pytest.approx(6e-9))
    assert tr.window_of(devs) == (0, 5)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    devices, host = tr.load(str(RECORDED))
    assert [d.device for d in devices] == [0]
    axpy = tr.op_count(devices, lambda k: k == "seeded_axpy_pallas")
    flash = tr.op_count(devices, lambda k: k == "flash_attention_pallas")
    assert axpy > 0 and flash > 0
    assert 0 < tr.busy_s(devices[0]) <= (
        tr.window_of(devices)[1] - tr.window_of(devices)[0]) * 1e-9
    assert any(n == "bench_window_open" for n, _, _ in host)
