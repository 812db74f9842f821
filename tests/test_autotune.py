"""Planner unit tests: cross-backend candidate legality, per-steps
remainder axis, cache round-trip, deterministic pick with a stubbed timer,
and the plan="auto" / serving wiring."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, stencils
from repro.core.api import StencilPlan, StencilProblem


@pytest.fixture()
def cache_path(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.json")
    # keep the module-level cache registry from leaking across tests
    monkeypatch.setattr(autotune, "_caches", {})
    return path


# ---------------------------------------------------------------------------
# candidate legality — the unified (jnp + pallas) pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,shape", [
    ("1d3p", (128,)), ("1d5p", (256,)), ("2d5p", (32, 64)),
    ("3d7p", (8, 8, 64)),
])
def test_candidates_are_legal(name, shape):
    spec = stencils.make(name)
    cands = autotune.candidate_plans(spec, shape)      # backend="auto"
    assert cands, "search space must not be empty"
    n = shape[-1]
    backends = {p.backend for p in cands}
    assert backends == {"jnp", "pallas", "mxu"}, backends
    for p in cands:
        if p.backend == "pallas":
            assert autotune.pallas_plan_legal(spec, shape, p.vl, p.m,
                                              p.t0), p
            continue
        if p.backend == "mxu":
            assert autotune.mxu_plan_legal(spec, shape, p.vl, p.m,
                                           k=p.k, ttile=p.ttile), p
            continue
        if p.scheme in ("transpose", "dlt") and p.k == 1 \
                and p.tiling == "none":
            m = p.m or (n // p.vl if p.scheme == "dlt" else p.vl)
            assert n % (p.vl * m) == 0, p
            assert m >= spec.r, p
        if p.tiling == "tessellate":
            h = p.height or p.k
            assert p.tile is not None
            for dim, t in zip(shape, p.tile):
                assert dim % t == 0, p
                assert t >= 2 * h * spec.r + 1, p
    # the historical default's shape is reachable
    assert StencilPlan(scheme="transpose", k=2, vl=8) \
        == StencilProblem(name, shape).default_plan()


def test_candidates_every_jnp_plan_runs_and_is_correct():
    prob = StencilProblem("2d5p", (16, 32))
    x = prob.init(0)
    want = np.asarray(prob.reference(x, 3))     # 3: not divisible by k=2,4
    for p in autotune.candidate_plans(prob.spec, prob.shape, backend="jnp",
                                      steps=3):
        got = np.asarray(prob.run(x, 3, p))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=str(p))


def test_pallas_candidates_run_and_are_correct():
    """A sample of the Pallas pool (interpret mode) — both remainder
    policies, 1-D and n-D — must reproduce the periodic reference."""
    for name, shape in [("1d3p", (32,)), ("2d5p", (8, 64))]:
        prob = StencilProblem(name, shape)
        x = prob.init(0)
        want = np.asarray(prob.reference(x, 3))
        cands = autotune.candidate_plans(prob.spec, shape,
                                         backend="pallas", steps=3)
        assert cands
        assert {p.remainder for p in cands if p.k > 1} \
            == {"fused", "native"}
        for p in cands[::5] + cands[-1:]:
            got = np.asarray(prob.run(x, 3, p))
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                       err_msg=str(p))


def test_pallas_pool_covers_nd_and_non_power_of_two_blocks():
    """Regression: the pallas pool used to stop at 1-D and could only
    reach power-of-two vl*m blocks; now n-D candidates exist and a
    non-power-of-two extent gets non-power-of-two (legal) blocks."""
    cands = autotune.candidate_plans(stencils.make("2d5p"), (32, 64),
                                     backend="pallas")
    assert cands and all(p.backend == "pallas" for p in cands)
    assert all(p.t0 is not None and 32 % p.t0 == 0 for p in cands)
    # n=160: vl=8 pairs include m=5 (vl*m=40, 160 % 40 == 0)
    spec = stencils.make("1d3p")
    cands = autotune.candidate_plans(spec, (160,), backend="pallas")
    assert any((p.vl * (p.m or 0)) & (p.vl * (p.m or 0) - 1) for p in cands), \
        "expected a non-power-of-two vl*m candidate for n=160"
    for p in cands:
        assert 160 % (p.vl * p.m) == 0, p


def test_pallas_legality_gate_rejects_bad_blocks():
    """The explicit gate rejects block shapes that don't divide the
    (transposed) array, halos that don't fit, and bad pipeline tiles —
    and everything the enumerator emits passes it."""
    spec1, spec2 = stencils.make("1d5p"), stencils.make("2d5p")
    assert not autotune.pallas_plan_legal(spec1, (160,), 8, 6)   # 48 ∤ 160
    assert not autotune.pallas_plan_legal(spec1, (160,), 8, 1)   # m < r
    assert not autotune.pallas_plan_legal(spec2, (30, 64), 8, 4, t0=4)  # 4∤30
    assert not autotune.pallas_plan_legal(spec2, (32, 64), 8, 4, t0=None)
    assert autotune.pallas_plan_legal(spec1, (160,), 8, 5)       # 40 | 160
    assert autotune.pallas_plan_legal(spec2, (32, 64), 8, 4, t0=4)
    for name, shape in [("1d3p", (96,)), ("1d5p", (160,)),
                        ("2d9p", (24, 96)), ("3d7p", (8, 4, 64))]:
        spec = stencils.make(name)
        for p in autotune.candidate_plans(spec, shape, backend="pallas"):
            assert autotune.pallas_plan_legal(spec, shape, p.vl, p.m,
                                              p.t0), p


def test_pallas_pool_fans_out_along_sweep_axis():
    """Every pallas (vl, m, t0, k) point exists in BOTH sweep engines, the
    legality gate validates the engine name, and the roofline ranks the
    resident twin ahead of its roundtrip sibling (it amortizes the layout
    round-trip over the run)."""
    import dataclasses

    from repro.roofline.stencil import estimate_plan_time

    for name, shape in [("1d3p", (128,)), ("2d5p", (32, 64))]:
        spec = stencils.make(name)
        cands = autotune.candidate_plans(spec, shape, backend="pallas",
                                         steps=16)
        assert {p.sweep for p in cands} == {"resident", "roundtrip"}
        by_key = {(p.vl, p.m, p.t0, p.k, p.remainder, p.sweep)
                  for p in cands}
        for p in cands:
            twin = ("roundtrip" if p.sweep == "resident" else "resident")
            assert (p.vl, p.m, p.t0, p.k, p.remainder, twin) in by_key, p
            if p.sweep == "resident":
                rt = dataclasses.replace(p, sweep="roundtrip")
                assert estimate_plan_time(spec, shape, 4, p, steps=16) < \
                    estimate_plan_time(spec, shape, 4, rt, steps=16), p
    assert not autotune.pallas_plan_legal(stencils.make("1d3p"), (128,),
                                          8, 8, sweep="bogus")


def test_resident_winner_round_trips_and_dispatches(cache_path):
    """A resident-sweep winner survives the cache round-trip and runs
    correctly through StencilProblem.run / plan='auto'."""
    prob = StencilProblem("1d3p", (128,))

    def resident_wins(fn, plan):
        return 0.001 if (plan.backend, plan.sweep) == \
            ("pallas", "resident") else 1.0

    res = autotune.tune(prob, cache_path=cache_path, timer=resident_wins)
    assert (res.plan.backend, res.plan.sweep) == ("pallas", "resident")
    res2 = autotune.tune(prob, cache_path=cache_path, timer=resident_wins)
    assert res2.cached and res2.plan.sweep == "resident"
    x = prob.init(0)
    got = prob.run(x, 5, res2.plan)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(prob.reference(x, 5)),
                               rtol=2e-5, atol=2e-5)


def test_interpret_budget_gate_off_tpu():
    """Off-TPU the auto pool skips pallas above the interpret-mode
    measurement budget (tuning a huge grid must not take minutes), but an
    explicit backend="pallas" request still enumerates.  The mxu engine
    is jnp-level (compiled XLA, no interpret mode) so it stays in the
    pool at any size — only its own operator-bytes budget gates it."""
    spec = stencils.make("1d3p")
    big = (autotune.INTERPRET_MAX_POINTS * 2,)
    auto = autotune.candidate_plans(spec, big)
    assert auto and all(p.backend in ("jnp", "mxu") for p in auto)
    assert not any(p.backend == "pallas" for p in auto)
    assert autotune.candidate_plans(spec, big, backend="pallas")


def test_per_steps_remainder_axis():
    """steps divisible by every k → no remainder variants; a remainder
    fans k>1 candidates out along the (k, remainder) axis."""
    spec = stencils.make("1d3p")
    flat = autotune.candidate_plans(spec, (128,), steps=8)
    assert all(p.remainder == "fused" for p in flat)
    ragged = autotune.candidate_plans(spec, (128,), steps=5)
    pallas_k2 = [p for p in ragged
                 if p.backend == "pallas" and p.k == 2]
    assert {p.remainder for p in pallas_k2} == {"fused", "native"}
    # jnp unroll: both policies coincide (fused multisteps) → no fan-out
    jnp_k2 = [p for p in ragged if p.backend == "jnp" and p.k == 2
              and p.tiling == "none"]
    assert {p.remainder for p in jnp_k2} == {"fused"}


# ---------------------------------------------------------------------------
# cache round-trip
# ---------------------------------------------------------------------------

def test_cache_roundtrip(cache_path):
    plan = StencilPlan(scheme="transpose", k=4, vl=8, m=4,
                       tiling="tessellate", tile=(16, 16), height=4,
                       remainder="native")
    rec = {"plan": autotune.plan_to_dict(plan), "seconds_per_step": 1e-5,
           "n_candidates": 9, "n_measured": 3, "measurements": []}
    c = autotune.PlanCache(cache_path)
    c.put("k1", rec)
    c.save()

    c2 = autotune.PlanCache(cache_path)
    got = c2.get("k1")
    assert autotune.plan_from_dict(got["plan"]) == plan
    assert got["seconds_per_step"] == 1e-5
    # file is the documented format
    raw = json.load(open(cache_path))
    assert raw["version"] == autotune.CACHE_VERSION
    assert "k1" in raw["entries"]


def test_cache_save_merges_concurrent_writers(cache_path):
    rec = lambda s: {"plan": autotune.plan_to_dict(StencilPlan(scheme=s)),
                     "seconds_per_step": 1.0}
    a = autotune.PlanCache(cache_path)
    b = autotune.PlanCache(cache_path)      # loaded before a saved
    a.put("ka", rec("reorg"))
    a.save()
    b.put("kb", rec("fused"))
    b.save()                                # must not erase a's entry
    c = autotune.PlanCache(cache_path)
    assert c.get("ka") is not None and c.get("kb") is not None


def test_cached_plan_sees_external_writer(cache_path):
    """A long-lived process (serving host) must pick up cache entries
    written by another process after its first (miss) lookup."""
    prob = StencilProblem("1d3p", (128,))
    assert autotune.cached_plan(prob, cache_path=cache_path) is None
    # simulate an offline tuner in another process: fresh PlanCache object
    writer = autotune.PlanCache(cache_path)
    plan = StencilPlan(scheme="reorg", k=1)
    key = autotune.plan_key("1d3p", (128,), prob.dtype, "auto")
    writer.put(key, {"plan": autotune.plan_to_dict(plan),
                     "seconds_per_step": 1e-5})
    writer.save()
    assert autotune.cached_plan(prob, cache_path=cache_path) == plan
    # an offline RE-tune of the already-loaded key must also be picked up
    # (loaded-from-disk entries must not shadow newer disk contents)
    better = StencilPlan(scheme="multiload", k=1)
    writer2 = autotune.PlanCache(cache_path)
    writer2.put(key, {"plan": autotune.plan_to_dict(better),
                      "seconds_per_step": 1e-6})
    writer2.save()
    assert autotune.cached_plan(prob, cache_path=cache_path) == better


def test_cached_plan_per_steps_falls_back_to_generic(cache_path):
    """Lookup order: the per-steps key wins over the generic key; a
    per-steps miss degrades to the generic plan, never to None."""
    prob = StencilProblem("1d3p", (128,))
    generic = StencilPlan(scheme="reorg", k=1)
    specific = StencilPlan(scheme="multiload", k=1)
    w = autotune.PlanCache(cache_path)
    w.put(autotune.plan_key("1d3p", (128,), prob.dtype, "auto"),
          {"plan": autotune.plan_to_dict(generic), "seconds_per_step": 1.0})
    w.put(autotune.plan_key("1d3p", (128,), prob.dtype, "auto", steps=7),
          {"plan": autotune.plan_to_dict(specific), "seconds_per_step": 1.0})
    w.save()
    assert autotune.cached_plan(prob, steps=7,
                                cache_path=cache_path) == specific
    assert autotune.cached_plan(prob, steps=9,
                                cache_path=cache_path) == generic
    assert autotune.cached_plan(prob, cache_path=cache_path) == generic


def test_cache_tolerates_corrupt_file(cache_path):
    with open(cache_path, "w") as f:
        f.write("{not json")
    assert autotune.PlanCache(cache_path).get("anything") is None


# ---------------------------------------------------------------------------
# deterministic pick with a stubbed timer
# ---------------------------------------------------------------------------

def test_deterministic_pick_and_cache_hit(cache_path):
    prob = StencilProblem("1d3p", (256,))
    target = StencilPlan(scheme="reorg", k=1)
    calls = []

    def stub_timer(fn, plan):
        calls.append(plan)
        return 0.001 if plan == target else 1.0

    res = autotune.tune(prob, cache_path=cache_path, timer=stub_timer,
                        max_measure=500)
    assert res.plan == target
    assert not res.cached
    assert res.n_measured == len(calls) > 1
    assert [m["plan"] for m in res.measurements] \
        == [autotune.plan_to_dict(p) for p in calls]

    # second run: cache hit, timer NEVER invoked again
    n = len(calls)
    res2 = autotune.tune(prob, cache_path=cache_path, timer=stub_timer,
                         max_measure=500)
    assert res2.cached and res2.plan == target
    assert len(calls) == n

    # force=True re-measures
    res3 = autotune.tune(prob, cache_path=cache_path, timer=stub_timer,
                         max_measure=500, force=True)
    assert not res3.cached and len(calls) > n


def test_unified_pool_measures_both_backends(cache_path):
    """The cross-backend search must put >=1 Pallas candidate in front of
    the timer even when the roofline ranks them last (stratification) —
    and a Pallas winner is returned when it measures fastest."""
    prob = StencilProblem("1d3p", (128,))
    seen = []

    def pallas_wins(fn, plan):
        seen.append(plan)
        return 0.001 if plan.backend == "pallas" else 1.0

    res = autotune.tune(prob, cache_path=cache_path, timer=pallas_wins)
    assert any(p.backend == "pallas" for p in seen)
    assert any(p.backend == "jnp" for p in seen)
    assert res.plan.backend == "pallas"
    # the winner round-trips through the cache with its backend intact
    res2 = autotune.tune(prob, cache_path=cache_path, timer=pallas_wins)
    assert res2.cached and res2.plan.backend == "pallas"


def test_failed_candidate_is_recorded(cache_path):
    """A candidate that raises while measured (on the chip: a kernel
    Mosaic refuses) is recorded with its error in the result and in the
    cache record, and the search goes on without it."""
    prob = StencilProblem("1d3p", (128,))

    def pallas_refused(fn, plan):
        if plan.backend == "pallas":
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        return 1.0

    res = autotune.tune(prob, cache_path=cache_path, timer=pallas_refused)
    assert res.failures
    assert all(p.backend == "pallas" for p, _ in res.failures)
    assert all("Mosaic failed" in err for _, err in res.failures)
    assert res.plan.backend != "pallas"
    assert all(m["plan"]["backend"] != "pallas" for m in res.measurements)
    with open(cache_path) as f:
        record = json.load(f)["entries"][res.key]
    assert [r["plan"] for r in record["failures"]] == [
        autotune.plan_to_dict(p) for p, _ in res.failures]


def test_backend_restriction_is_honored(cache_path):
    prob = StencilProblem("1d3p", (128,))
    res = autotune.tune(prob, backend="jnp", cache_path=cache_path,
                        timer=lambda fn, p: 1.0)
    assert all(m["plan"]["backend"] == "jnp" for m in res.measurements)
    res = autotune.tune(prob, backend="pallas", cache_path=cache_path,
                        timer=lambda fn, p: 1.0)
    assert all(m["plan"]["backend"] == "pallas" for m in res.measurements)


def test_default_plan_always_in_measured_pool(cache_path):
    prob = StencilProblem("2d5p", (32, 64))
    seen = []
    autotune.tune(prob, cache_path=cache_path,
                  timer=lambda fn, p: (seen.append(p), 1.0)[1],
                  max_measure=3)
    assert prob.default_plan() in seen


def test_per_steps_key_separates_tunings(cache_path):
    """Tuning for steps=5 and steps=None lands in distinct cache entries;
    each later lookup hits its own."""
    prob = StencilProblem("1d3p", (128,))
    timer = lambda fn, p: 1.0
    r5 = autotune.tune(prob, steps=5, cache_path=cache_path, timer=timer)
    rg = autotune.tune(prob, cache_path=cache_path, timer=timer)
    assert r5.key != rg.key
    assert autotune.tune(prob, steps=5, cache_path=cache_path,
                         timer=timer).cached
    assert autotune.tune(prob, cache_path=cache_path, timer=timer).cached


def test_measure_window_does_not_scale_with_steps(cache_path):
    """Tuning cost must not grow with the run length: divisible steps
    measure the default 4-step window; ragged steps measure a short
    window congruent mod every block size (lcm + steps % lcm), never the
    full run."""
    prob = StencilProblem("1d3p", (128,))
    timer = lambda fn, p: 100.0
    res = autotune.tune(prob, steps=100, cache_path=cache_path, timer=timer)
    assert res.seconds_per_step == pytest.approx(100.0 / 4)
    res = autotune.tune(prob, steps=5, cache_path=cache_path, timer=timer)
    assert res.seconds_per_step == pytest.approx(100.0 / 5)
    res = autotune.tune(prob, steps=10001, cache_path=cache_path,
                        timer=timer)
    assert res.seconds_per_step == pytest.approx(100.0 / 5)  # 4 + 10001%4


def test_divisible_steps_collapse_to_generic_key(cache_path):
    """Step counts every candidate block divides share one cache entry:
    tuning for steps=8 then asking for steps=12, 16 or None are all
    cache hits (no per-value fragmentation / re-measuring)."""
    prob = StencilProblem("1d3p", (128,))
    timer = lambda fn, p: 1.0
    r8 = autotune.tune(prob, steps=8, cache_path=cache_path, timer=timer)
    assert not r8.cached and "|s*|" in r8.key
    for steps in (12, 16, None):
        assert autotune.tune(prob, steps=steps, cache_path=cache_path,
                             timer=timer).cached, steps
    assert autotune.cached_plan(prob, steps=12,
                                cache_path=cache_path) is not None


def test_failing_candidates_are_skipped(cache_path):
    prob = StencilProblem("1d3p", (256,))

    def flaky(fn, plan):
        if plan.k == 1:
            raise RuntimeError("boom")
        return 1.0

    res = autotune.tune(prob, cache_path=cache_path, timer=flaky)
    assert res.plan.k > 1


# ---------------------------------------------------------------------------
# plan="auto" wiring + serving path
# ---------------------------------------------------------------------------

def test_run_auto_measures_writes_cache_and_is_correct(
        cache_path, monkeypatch):
    monkeypatch.setenv(autotune.CACHE_ENV, cache_path)
    prob = StencilProblem("1d3p", (128,))
    x = prob.init(0)
    got = prob.run(x, 5, plan="auto")
    want = prob.reference(x, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # observable tuning artifact: the cache file records the search,
    # keyed per-steps and stamped with the code fingerprint
    raw = json.load(open(cache_path))
    (key, rec), = raw["entries"].items()
    assert key.startswith("1d3p|128|float32|auto|")
    assert f"|s5|{autotune.code_fingerprint()}" in key
    assert rec["fingerprint"] == autotune.code_fingerprint()
    assert rec["n_measured"] >= 1 and rec["measurements"]
    # the unified pool put a pallas candidate in front of the timer
    assert any(m["plan"]["backend"] == "pallas"
               for m in rec["measurements"])


def test_stencil_service_uses_cached_plan_never_measures(
        cache_path, monkeypatch):
    from repro.serve.engine import StencilService

    prob = StencilProblem("1d3p", (128,))
    tuned = StencilPlan(scheme="reorg", k=1)
    autotune.tune(prob, cache_path=cache_path, max_measure=500,
                  timer=lambda fn, p: 0.001 if p == tuned else 1.0)

    svc = StencilService(cache_path=cache_path)
    assert svc.plan_for("1d3p", (128,)) == tuned

    def no_measure(*a, **kw):
        raise AssertionError("serving path must not measure")
    monkeypatch.setattr(autotune, "tune", no_measure)
    x = prob.init(0)
    got = svc.sweep("1d3p", x, 4)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(prob.reference(x, 4)),
                               rtol=2e-5, atol=2e-5)
    # cold signature (not in cache) falls back to the static default
    assert svc.plan_for("1d3p", (256,)) \
        == StencilProblem("1d3p", (256,)).default_plan()


def test_stencil_service_picks_up_later_per_steps_tuning(cache_path):
    """A per-steps request served by the generic fallback must not pin
    that step count: once an offline tuner writes the per-steps entry,
    the next request serves it."""
    from repro.serve.engine import StencilService

    prob = StencilProblem("1d3p", (128,))
    generic = StencilPlan(scheme="reorg", k=1)
    w = autotune.PlanCache(cache_path)
    w.put(autotune.plan_key("1d3p", (128,), prob.dtype, "auto"),
          {"plan": autotune.plan_to_dict(generic), "seconds_per_step": 1.0})
    w.save()
    svc = StencilService(cache_path=cache_path)
    assert svc.plan_for("1d3p", (128,), steps=7) == generic
    # offline tuner fills the per-steps entry afterwards
    specific = StencilPlan(scheme="multiload", k=1)
    w2 = autotune.PlanCache(cache_path)
    w2.put(autotune.plan_key("1d3p", (128,), prob.dtype, "auto", steps=7),
           {"plan": autotune.plan_to_dict(specific),
            "seconds_per_step": 1.0})
    w2.save()
    assert svc.plan_for("1d3p", (128,), steps=7) == specific
    assert svc.plan_for("1d3p", (128,), steps=9) == generic


def test_stencil_service_dispatches_pallas_backend(cache_path, monkeypatch):
    """A Pallas winner tuned offline flows through the serving path to the
    kernels with no caller changes — and serving still never measures."""
    from repro.serve.engine import StencilService

    prob = StencilProblem("1d3p", (128,))
    autotune.tune(prob, cache_path=cache_path,
                  timer=lambda fn, p: 0.001 if p.backend == "pallas"
                  else 1.0)
    svc = StencilService(cache_path=cache_path)
    plan = svc.plan_for("1d3p", (128,))
    assert plan.backend == "pallas"

    monkeypatch.setattr(autotune, "tune", lambda *a, **kw: (_ for _ in ())
                        .throw(AssertionError("no measuring")))
    x = prob.init(0)
    got = svc.sweep("1d3p", x, 4)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(prob.reference(x, 4)),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# background warm tuning (StencilService.warm_async)
# ---------------------------------------------------------------------------

def test_warm_async_tunes_off_request_path(cache_path, monkeypatch):
    """warm_async fills the plan cache from a worker thread; afterwards
    the serving path gets the tuned plan WITHOUT ever measuring."""
    import threading

    from repro.serve.engine import StencilService

    svc = StencilService(cache_path=cache_path)
    main_thread = threading.current_thread()
    tuned = StencilPlan(scheme="reorg", k=1)
    measured_on = []

    def stub_timer(fn, plan):
        measured_on.append(threading.current_thread())
        return 0.001 if plan == tuned else 1.0

    # cold signature: the request path degrades to the default — never
    # blocks on the in-flight warm
    assert svc.plan_for("1d3p", (128,)) \
        == StencilProblem("1d3p", (128,)).default_plan()

    fut = svc.warm_async("1d3p", (128,), timer=stub_timer,
                         max_measure=500)
    assert fut.result(timeout=60) == tuned
    assert measured_on and all(t is not main_thread for t in measured_on)

    # serving path now sees the tuned plan, with measuring forbidden
    monkeypatch.setattr(autotune, "tune", lambda *a, **kw: (_ for _ in ())
                        .throw(AssertionError("serving must not measure")))
    assert svc.plan_for("1d3p", (128,)) == tuned
    x = StencilProblem("1d3p", (128,)).init(0)
    got = svc.sweep("1d3p", x, 4)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(StencilProblem("1d3p", (128,)).reference(x, 4)),
        rtol=2e-5, atol=2e-5)
    # ...and the cache file itself was populated (visible cross-process)
    assert autotune.cached_plan(StencilProblem("1d3p", (128,)),
                                cache_path=cache_path) == tuned


def test_warm_async_coalesces_inflight_duplicates(cache_path):
    import threading

    from repro.serve.engine import StencilService

    svc = StencilService(cache_path=cache_path)
    release = threading.Event()
    calls = []

    def slow_timer(fn, plan):
        calls.append(plan)
        release.wait(timeout=30)
        return 1.0

    f1 = svc.warm_async("1d3p", (128,), steps=5, timer=slow_timer)
    f2 = svc.warm_async("1d3p", (128,), steps=5, timer=slow_timer)
    assert f1 is f2                       # same in-flight future
    release.set()
    f1.result(timeout=60)
    n = len(calls)
    # a re-warm after completion is a cheap cache hit (no new measuring)
    f3 = svc.warm_async("1d3p", (128,), steps=5, timer=slow_timer)
    assert f3.result(timeout=60) is not None
    assert len(calls) == n


def test_warm_async_close_cancels_queued_warms(cache_path):
    """close() bounds shutdown: queued warms are cancelled, the in-flight
    tune completes (and still publishes), warm_async then refuses; the
    serving path keeps working after close."""
    import threading

    from repro.serve.engine import StencilService

    svc = StencilService(cache_path=cache_path)
    started = threading.Event()
    release = threading.Event()

    def slow_timer(fn, plan):
        started.set()
        release.wait(timeout=30)
        return 1.0

    inflight = svc.warm_async("1d3p", (128,), timer=slow_timer)
    assert started.wait(timeout=30)
    queued = svc.warm_async("1d3p", (256,), timer=slow_timer)
    svc.close(wait=False)                 # cancel queued, don't block...
    assert queued.cancelled()
    release.set()                         # ...then let the in-flight land
    assert inflight.result(timeout=60) is not None
    with pytest.raises(RuntimeError, match="closed"):
        svc.warm_async("1d3p", (128,))
    # serving still answers (cache filled by the in-flight warm)
    x = StencilProblem("1d3p", (128,)).init(0)
    got = svc.sweep("1d3p", x, 4)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(StencilProblem("1d3p", (128,)).reference(x, 4)),
        rtol=2e-5, atol=2e-5)
    svc.close()                           # idempotent


def test_warm_async_close_race_late_publish_is_noop(cache_path):
    """Regression for the close()/warm_async race: a tune still in flight
    when close() returns (close(wait=False)) must (a) keep its future
    usable — it resolves to the tuned plan, (b) persist the winner to the
    shared cache file, and (c) NOT repopulate the closed service's
    in-process memo; close() drains the in-flight map under the lock so
    no stale future is ever handed out."""
    import threading

    from repro.serve.engine import StencilService

    svc = StencilService(cache_path=cache_path)
    started = threading.Event()
    release = threading.Event()

    def slow_timer(fn, plan):
        started.set()
        release.wait(timeout=30)
        return 0.001

    fut = svc.warm_async("1d3p", (128,), timer=slow_timer)
    assert started.wait(timeout=30)
    svc.close(wait=False)                 # tune is mid-measurement
    with svc._lock:
        assert not svc._warming           # drained under the lock
    release.set()
    plan = fut.result(timeout=60)         # the caller still gets the plan
    assert isinstance(plan, StencilPlan)
    # the winner reached the shared cache file (visible cross-process)...
    assert autotune.cached_plan(StencilProblem("1d3p", (128,)),
                                cache_path=cache_path) == plan
    # ...but the late publish into the closed service was a no-op
    with svc._lock:
        assert not svc._plans
        assert not svc._warming
    svc.close()                           # idempotent after the race


# ---------------------------------------------------------------------------
# temporal-tile (ttile) axis
# ---------------------------------------------------------------------------

def test_ttile_legality_gate():
    """ttile_plan_legal: resident engines only; the depth-ttile·k halo
    slope must fit the pipelined extent; the run must be deep enough to
    amortize; the VMEM window must fit the budget."""
    import dataclasses

    spec = stencils.make("1d3p")
    base = StencilPlan(scheme="transpose", k=2, vl=8, m=8,
                       backend="pallas", sweep="resident")
    tiled = dataclasses.replace(base, ttile=4)
    assert autotune.ttile_plan_legal(spec, (2048,), base)        # ttile=1
    assert autotune.ttile_plan_legal(spec, (2048,), tiled, steps=16)
    # not enough steps to run one full ttile·k block
    assert not autotune.ttile_plan_legal(spec, (2048,), tiled, steps=6)
    # roundtrip / jnp backends never time-tile
    assert not autotune.ttile_plan_legal(
        spec, (2048,), dataclasses.replace(tiled, sweep="roundtrip"))
    assert not autotune.ttile_plan_legal(
        spec, (2048,), StencilPlan(scheme="fused", k=2, ttile=2))
    # slope exceeds the extent: depth·r = 8 > 4 rows
    spec2 = stencils.make("2d5p")
    deep = StencilPlan(scheme="transpose", k=2, vl=8, m=4, t0=4,
                       backend="pallas", sweep="resident", ttile=4)
    assert not autotune.ttile_plan_legal(spec2, (4, 64), deep)
    assert autotune.ttile_plan_legal(spec2, (64, 64), deep)
    # distributed: the decomposed-axis shard extent bounds the slope
    dist = StencilPlan(scheme="fused", k=2, backend="distributed",
                       decomp=(8,), ttile=4)
    assert autotune.ttile_plan_legal(spec, (256,), dist)     # nl=32 >= 8
    assert not autotune.ttile_plan_legal(spec, (32,), dist)  # nl=4 < 8
    # VMEM: a deep tile on a fat 3-D block needs more than a kernel may
    # claim; a thin tile at half the depth fits
    spec3 = stencils.make("3d27p")
    fat = StencilPlan(scheme="transpose", k=2, vl=128, m=1, t0=8,
                      backend="pallas", sweep="resident", ttile=4)
    assert not autotune.ttile_plan_legal(spec3, (256,) * 3, fat)
    assert autotune.ttile_plan_legal(
        spec3, (256,) * 3, dataclasses.replace(fat, t0=1, ttile=2))


def test_pallas_pool_fans_out_along_ttile_axis():
    """Resident candidates fan out over ttile ∈ _TTILES (roundtrip stays
    ttile=1); the roofline ranks a deep-run ttile plan ahead of its
    ttile=1 twin; the field round-trips through the plan-dict codec and
    old dicts (no "ttile" key) still load."""
    import dataclasses

    from repro.roofline.stencil import estimate_plan_time

    spec = stencils.make("1d3p")
    cands = autotune.candidate_plans(spec, (2048,), backend="pallas",
                                     steps=16)
    tts = {p.ttile for p in cands if p.sweep == "resident"}
    assert tts >= {1, 2, 4}, tts
    assert all(p.ttile == 1 for p in cands if p.sweep == "roundtrip")
    for p in cands:
        if p.ttile > 1:
            assert autotune.ttile_plan_legal(spec, (2048,), p, steps=16), p
    tiled = next(p for p in cands if p.ttile == 4 and p.k == 2)
    base = dataclasses.replace(tiled, ttile=1)
    assert estimate_plan_time(spec, (1 << 20,), 4, tiled, steps=32) < \
        estimate_plan_time(spec, (1 << 20,), 4, base, steps=32)
    d = autotune.plan_to_dict(tiled)
    assert d["ttile"] == 4
    assert autotune.plan_from_dict(d) == tiled
    del d["ttile"]
    assert autotune.plan_from_dict(d).ttile == 1


def test_ttile_winner_round_trips_and_dispatches(cache_path):
    """A ttile>1 winner survives the cache round-trip and runs bit-
    identically to its ttile=1 twin through plan='auto' dispatch."""
    import dataclasses

    prob = StencilProblem("1d3p", (128,))

    def ttile_wins(fn, plan):
        # pin the PALLAS ttile=2 twin: an mxu ttile winner would be
        # rounding-level (not bitwise) vs its ttile=1 twin — the matmul
        # reassociates — and this test asserts array_equal
        return 0.001 if (plan.ttile, plan.backend) == (2, "pallas") \
            else 1.0

    res = autotune.tune(prob, steps=16, cache_path=cache_path,
                        timer=ttile_wins, max_measure=500)
    assert res.plan.ttile == 2 and res.plan.sweep == "resident", res.plan
    res2 = autotune.tune(prob, steps=16, cache_path=cache_path,
                         timer=ttile_wins)
    assert res2.cached and res2.plan == res.plan
    x = prob.init(0)
    got = np.asarray(prob.run(x, 16, res2.plan))
    ref = np.asarray(prob.run(x, 16,
                              dataclasses.replace(res2.plan, ttile=1)))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_allclose(got, np.asarray(prob.reference(x, 16)),
                               rtol=5e-5, atol=5e-5)


def test_measured_search_prefers_ttile1_when_tiling_times_slower(
        cache_path):
    """The interpret-mode regression from the smoke bench: on hosts
    where temporal tiling measures SLOWER (the bench rows carry
    mode='interpret' for exactly this reason), the measured search must
    return a ttile=1 winner — the roofline's deep-run preference for
    ttile>1 is advisory ranking, never an override of the timer."""
    prob = StencilProblem("1d3p", (128,))

    def tiling_slower(fn, plan):
        # interpret-mode cost profile: every extra ttile level retraces
        return 1.0 + 10.0 * (plan.ttile - 1)

    res = autotune.tune(prob, steps=16, cache_path=cache_path,
                        timer=tiling_slower, max_measure=500)
    assert res.plan.ttile == 1, res.plan
    # the pool did offer tiled candidates — the timer rejected them,
    # they weren't gated away
    assert any(p.ttile > 1 for p in autotune.candidate_plans(
        stencils.make("1d3p"), (128,), backend="pallas", steps=16))
    res2 = autotune.tune(prob, steps=16, cache_path=cache_path,
                         timer=tiling_slower)
    assert res2.cached and res2.plan.ttile == 1


def test_native_remainder_gate_is_schedule_aware():
    """The remainder-legality fix: a plan whose remainder='native' block
    is deeper than the grid supports is rejected AT ENUMERATION; a plan
    whose k exceeds steps is judged by the blocks that actually run."""
    spec = stencils.make("1d3p")
    # k=16 on a 12-row pipelined extent: steps=12 never runs the k-block,
    # only the depth-12 native remainder — legal on n_pipe=2048
    assert autotune.pallas_plan_legal(spec, (2048,), 8, 8, None,
                                      "resident", k=16, steps=12,
                                      remainder="native")
    # the enumerated pool never carries a native variant whose schedule
    # depth exceeds the extent
    spec2 = stencils.make("2d5p")
    for p in autotune.candidate_plans(spec2, (8, 64), backend="pallas",
                                      steps=7):
        kmax = autotune._schedule_max_depth(p.k, 7, p.remainder, p.ttile)
        assert kmax * spec2.r <= 8, p
    # distributed: nl=8, k=16 illegal outright; steps=12 native still
    # needs a depth-12 block (> nl) -> illegal; fused (12 single steps)
    # is fine
    assert not autotune.distributed_plan_legal(spec, (64,), (8,), 16,
                                               n_devices=8)
    assert not autotune.distributed_plan_legal(spec, (64,), (8,), 16,
                                               n_devices=8, steps=12,
                                               remainder="native")
    assert autotune.distributed_plan_legal(spec, (64,), (8,), 16,
                                           n_devices=8, steps=12,
                                           remainder="fused")
