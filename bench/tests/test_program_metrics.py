"""The metrics that read the program's own spans (``repro.*`` host events)
and kernel tags (``{"repro": ...}`` in a custom call's text): on a
synthetic window, and on traces recorded on a TPU v5e with and without
them."""
import types
from pathlib import Path

import pytest

from bench import cells, trace
from bench.trace import Op, Reduced

DATA = Path(__file__).resolve().parent / "data"
U = 3e-3       # s: the synthetic window's unit; its calls lie 15 ms apart
NEW = ("plan_ms_p50", "dispatch_ms_p50", "host_bound_frac",
       "sweep_kernel_roofline", "glue_frac", "sweep_launches_per_call")


def kernel(name, start, end, operand, tag):
    return Op(name, start, end,
              f"%{name} = f32[8] custom-call(f32[8] %{operand}), "
              'custom_call_target="tpu_custom_call", '
              f'frontend_attributes={{kernel_metadata={{\n"repro":"{tag}"\n}}}}',
              "custom-call")


def synthetic() -> Reduced:
    """Two calls in a window [0, 10) (in units of ``U``) on one device,
    whose clock reads 0.2 early against the host's.  Device: call 1 runs
    a copy, a sweep and a copy over [1, 3.5); call 2 a layout kernel and
    a loop of two sweeps over [5.6, 9).  Idle: [0, 1), [3.5, 5.6) and
    [9, 10).  The host launches the calls at 1.2 and 5.7, so call 1's
    first op comes 0.2 before its launch on the device's clock."""
    ops = [Op("copy.1", 1.0, 1.5, "%copy.1 = f32[8] copy(f32[8] %x.1)",
              "copy"),
           kernel("closed_call.4", 1.5, 3.0, "copy.1", "sweep"),
           Op("copy.2", 3.0, 3.5, "%copy.2 = f32[8] copy(f32[8] "
              "%closed_call.4)", "copy"),
           kernel("t.1", 5.6, 6.0, "bitcast.1", "layout"),
           Op("while", 6.0, 9.0, "%while = (s32[], f32[8]) while((s32[], "
              "f32[8]) %tuple.1), condition=%c, body=%b", "while"),
           kernel("closed_call.4", 6.1, 7.0, "copy.14", "sweep"),
           kernel("closed_call.4", 7.0, 8.5, "copy.14", "sweep")]
    host = [Op("bench.window", 0, 10),
            Op("bench.call", 0, 5), Op("bench.dispatch", 0, 2),
            Op("repro.run", 0.1, 1.9), Op("repro.plan", 0.2, 0.5),
            Op("repro.dispatch", 0.6, 1.8),
            Op("PJRT_LoadedExecutable_Execute linkage", 1.2, 1.3),
            Op("bench.wait", 2, 5),
            Op("bench.call", 5, 10), Op("bench.dispatch", 5, 6),
            Op("repro.run", 5.1, 5.9), Op("repro.plan", 5.2, 5.3),
            Op("repro.dispatch", 5.4, 5.8),
            Op("PJRT_LoadedExecutable_Execute linkage", 5.7, 5.75),
            Op("bench.wait", 6, 10)]
    for o in ops + host:
        o.start, o.end = o.start * U, o.end * U
    trace._nest(ops)
    return Reduced(window=(0.0, 10 * U), devices=[ops], host=host)


def context(r: Reduced, n_calls: int, **kw) -> types.SimpleNamespace:
    base = dict(trace=r, n_calls=n_calls, chips=1, vpu_ops_per_s=1e12,
                ops_per_call=0.975e12 * U, bytes_per_call=0.5e9 * U,
                peak={"hbm_bytes_per_s": 1e9}, note=lambda msg: None)
    return types.SimpleNamespace(**(base | kw))


def read(name, ctx):
    return cells.metric_reader(name).read(ctx)


def test_program_spans():
    ctx = context(synthetic(), 2)
    assert read("plan_ms_p50", ctx) == pytest.approx(
        (0.3 + 0.1) / 2 * U * 1e3)
    assert read("dispatch_ms_p50", ctx) == pytest.approx(
        (1.2 + 0.4) / 2 * U * 1e3)


def test_host_bound_counts_only_the_overlap_with_the_program():
    """Shifted 0.2 later, the device idles over [0, 1.2), [3.7, 5.8)
    and [9.2, 10).  The middle gap straddles call 1's ``bench.wait`` and
    call 2's ``repro.run`` [5.1, 5.9): it overlaps the program's span for
    0.7 of its 2.1, and its midpoint lies in the wait."""
    r = synthetic()
    ctx = context(r, 2)
    hb = cells.metric_reader("host_bound_frac")
    assert hb.causal_shift(r.window, r.devices[0],
                           [1.2 * U, 5.7 * U]) == pytest.approx(0.2 * U)
    # [0, 1.2) overlaps repro.run for 1.1, [3.7, 5.8) for 0.7, [9.2, 10)
    # not
    assert read("host_bound_frac", ctx) == pytest.approx(18.0)
    assert read("device_idle_frac", ctx) == pytest.approx(41.0)
    # the midpoint rule charges the whole straddling gap (2.1 on the
    # device's clock), and the last one (1.0), to the wait
    idle = dict(r.breakdown()["idle_gaps"])
    assert idle["bench.wait"] == pytest.approx((2.1 + 1.0) * U)


def test_each_call_takes_the_causal_bound_of_the_calls_around_it():
    """Sixty calls 30 ms apart, each one op of 20 ms that starts 0.05 ms
    after its launch; the device clock reads 0.3 ms early for the first
    ten calls and 0.1 ms early after them.  One shift for the window would put
    the later calls 0.2 late; each call's own lands its op on its launch
    (the least delay, taken as none) once the early calls lie more than
    ``NEAR`` calls away."""
    hb = cells.metric_reader("host_bound_frac")
    ms = 1e-3
    launches = [(10 + 30 * k) * ms for k in range(60)]
    early = [(0.3 if k < 10 else 0.1) * ms for k in range(60)]
    ops = [Op("c", t + 0.05 * ms - e, t + 20 * ms - e)
           for t, e in zip(launches, early)]
    window = (0.0, 1.9)
    assert hb.causal_shift(window, ops, launches) == pytest.approx(
        0.25 * ms)
    moved = hb.aligned(window, ops, launches)
    assert moved[0].start == pytest.approx(launches[0])
    late = range(10 + hb.NEAR, 60)
    assert [moved[k].start for k in late] == pytest.approx(
        [launches[k] for k in late])
    assert moved[10].start == pytest.approx(launches[10] + 0.2 * ms)


@pytest.mark.parametrize("launches", [
    [1.2, 3.6],             # a launch before the call ahead of it ended
    [1.2, 5.7, 7.5]])       # a launch while the device runs a call
def test_host_bound_reads_nothing_where_calls_and_launches_disagree(
        launches):
    """No shift within ``REACH`` puts every launch in an idle gap."""
    r = synthetic()
    r.host = [o for o in r.host if not o.name.startswith("PJRT")] + [
        Op("PJRT_LoadedExecutable_Execute linkage", t * U, t * U + 1e-5)
        for t in launches]
    ctx = context(r, 2)
    assert read("host_bound_frac", ctx) is None


def test_tagged_kernels():
    ctx = context(synthetic(), 2)
    # sweeps: 1.5 + 0.9 + 1.5 s; least time max(1.95, 1.0) s
    assert read("sweep_kernel_roofline", ctx) == pytest.approx(50.0)
    assert read("sweep_roofline", ctx) == pytest.approx(50.0)
    assert read("sweep_launches_per_call", ctx) == pytest.approx(1.5)
    # untagged: two copies (0.5 + 0.5) and the loop's own 0.6, of 5.9 busy
    assert read("glue_frac", ctx) == pytest.approx(100 * 1.6 / 5.9)


def test_a_kernel_is_selected_by_its_tag_not_its_operand():
    """A layout kernel fed by a parameter: the operand rule of
    ``sweep_roofline`` counts it as a sweep, the tag does not."""
    r = synthetic()
    r.devices[0][3] = kernel("t.1", 5.6 * U, 6.0 * U, "x.1", "layout")
    trace._nest(r.devices[0])
    ctx = context(r, 2)
    assert read("sweep_kernel_roofline", ctx) == pytest.approx(50.0)
    assert read("sweep_roofline", ctx) == pytest.approx(
        100 * 1.95 / 4.3)


def test_untagged_window_reads_nothing():
    r = synthetic()
    r.host = [o for o in r.host if not o.name.startswith("repro.")]
    for o in r.devices[0]:
        o.text = o.text.replace('"repro"', '"other"')
    ctx = context(r, 2)
    assert {m: read(m, ctx) for m in NEW} == dict.fromkeys(NEW)


def test_untagged_tpu_trace_reads_nothing():
    """The trace recorded before the program had spans and tags."""
    r = trace.reduce(str(DATA / "2d5p-snap8-1s.xplane.pb"))
    ctx = context(r, 46, vpu_ops_per_s=5.43e12)
    assert {m: read(m, ctx) for m in NEW} == dict.fromkeys(NEW)


def test_tagged_tpu_trace():
    """One second of ``2d5p.snap8`` on a TPU v5e with the program's spans
    and tags (46 calls of 8 steps, plan pallas/resident k=4 ttile=4: two
    sweep launches a call).  The tag selects exactly the ops that
    ``sweep_roofline``'s operand rule selects."""
    r = trace.reduce(str(DATA / "2d5p-snap8-tagged-1s.xplane.pb"))
    ctx = context(r, 46, ops_per_call=6 * 8 * 12288 ** 2,
                  bytes_per_call=2 * 4 * 12288 ** 2, vpu_ops_per_s=5.453e12,
                  peak={"hbm_bytes_per_s": 8.19e11})
    from bench.metrics import sweep_kernel_roofline as k
    from bench.metrics import sweep_roofline as s
    by_tag = r.select(k.KINDS, k.PATTERNS)
    assert by_tag == r.select(s.KINDS, s.PATTERNS, s.EXCLUDE)
    assert len(by_tag[0]) == 2 * 46
    assert read("sweep_kernel_roofline", ctx) == read("sweep_roofline", ctx)
    assert read("sweep_launches_per_call", ctx) == 2
    assert read("glue_frac", ctx) == pytest.approx(53.56, abs=0.01)
    assert read("plan_ms_p50", ctx) == pytest.approx(0.2697, abs=1e-4)
    assert read("dispatch_ms_p50", ctx) == pytest.approx(0.2426, abs=1e-4)
    host_bound = read("host_bound_frac", ctx)
    assert host_bound == pytest.approx(1.7614, abs=1e-4)
    assert host_bound <= read("device_idle_frac", ctx)
