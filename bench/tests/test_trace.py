"""Trace reduction: interval arithmetic on a synthetic window, and the
reduction of a small trace recorded on a TPU v5e."""
import types
from pathlib import Path

import pytest

from bench import cells, trace
from bench.trace import Op, Reduced

DATA = Path(__file__).resolve().parent / "data"


def synthetic() -> Reduced:
    # window [0, 10); device 0 busy [1,3) [2,4) [6,7); device 1 busy [0,10)
    d0 = [Op("k.1", 1, 3, "%k.1 = f32[8] custom-call(f32[8] %copy.1)",
             "custom-call"),
          Op("copy.1", 2, 4, "%copy.1 = f32[8] copy(f32[8] %x)", "copy"),
          Op("cp.2", 6, 7, "%cp.2 = f32[8] collective-permute-done(%cp.1)",
             "collective-permute-done")]
    d1 = [Op("k.1", 0, 10, "%k.1 = f32[8] custom-call(f32[8] %copy.1)",
             "custom-call")]
    for ops in (d0, d1):
        trace._nest(ops)
    host = [Op("bench.window", 0, 10), Op("bench.call", 0, 5),
            Op("bench.dispatch", 0, 2), Op("bench.wait", 2, 5),
            Op("bench.call", 5, 10), Op("bench.wait", 6, 10)]
    return Reduced(window=(0.0, 10.0), devices=[d0, d1], host=host)


def test_busy_is_the_union_averaged_over_devices():
    r = synthetic()
    assert r.window_s == 10
    assert r.busy_s == pytest.approx((3 + 1 + 10) / 2)


def test_layer_time():
    r = synthetic()
    assert r.layer_s("custom-call") == pytest.approx((2 + 10) / 2)
    assert r.layer_s("copy") == pytest.approx(2 / 2)
    assert r.layer_s("custom-call", (r"%copy\.1",)) == pytest.approx(6)
    assert r.layer_s("copy|custom-call", (), (r"%x\b",)) == pytest.approx(6)
    assert r.layer_s("collective-permute(-done)?") == pytest.approx(1 / 2)


def test_self_time_leaves_out_nested_ops():
    ops = [Op("while", 0, 10, kind="while"), Op("k", 1, 4, kind="custom-call"),
           Op("c", 5, 6, kind="copy"), Op("after", 10, 12, kind="copy")]
    trace._nest(ops)
    assert [o.self_s for o in ops] == [6, 3, 1, 2]


def test_parse_hlo():
    assert trace.parse_hlo(
        "%copy.4 = f32[12288,12288]{1,0:T(8,128)} copy(f32[12288,12288]"
        "{0,1:T(8,128)} %bitcast.3)") == ("copy.4", "copy")
    assert trace.parse_hlo(
        "%while = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) while((s32[],"
        " f32[8,128]) %tuple.10), condition=%c, body=%b") == ("while", "while")
    assert trace.parse_hlo("%cp.1 = (f32[8], u32[]) collective-permute-start"
                           "(f32[8] %x)")[1] == "collective-permute-start"
    assert trace.parse_hlo("bench.window") == ("bench.window", "")


def test_idle_gaps_are_labelled_by_the_open_host_event():
    r = synthetic()
    assert r.gaps() == [[(0, 1), (4, 6), (7, 10)], []]
    b = r.breakdown()
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"bench.dispatch": 0.5, "bench.call": 1.0, "bench.wait": 1.5})
    assert b["device_ops"][0] == ["k.1 custom-call", pytest.approx(6.0)]


def test_recorded_tpu_trace():
    """One second of ``2d5p.snap8`` on a TPU v5e (46 calls of 8 steps,
    plan pallas/resident k=4 ttile=4): sweep kernels, loop-carry copies
    and the four layout copies of each call, as the trace names them."""
    r = trace.reduce(str(DATA / "2d5p-snap8-1s.xplane.pb"))
    assert len(r.devices) == 1
    assert r.window_s == pytest.approx(1.0196, abs=1e-3)
    assert r.busy_s == pytest.approx(0.9540, abs=1e-3)
    ctx = types.SimpleNamespace(trace=r)
    layout = cells.metric_reader("layout_frac").read(ctx)
    idle = cells.metric_reader("device_idle_frac").read(ctx)
    assert layout == pytest.approx(35.3, abs=0.1)
    assert idle == pytest.approx(6.43, abs=0.01)
    from bench.metrics import sweep_roofline as s
    kernels = r.layer_s(s.KINDS, s.PATTERNS, s.EXCLUDE)
    assert kernels == pytest.approx(0.4483, abs=1e-3)
    assert len(r.select(s.KINDS, s.PATTERNS, s.EXCLUDE)[0]) == 2 * 46
    b = r.breakdown()
    assert b["device_ops"][0][0] == "closed_call.4 custom-call"
    assert 0 < len(b["device_ops"]) <= 10
    assert {label for label, _ in b["idle_gaps"]} <= {"bench.wait",
                                                     "bench.dispatch"}
