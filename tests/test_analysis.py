"""The static plan auditor (repro.analysis).

Four layers under test:

1. the shared recursive jaxpr walker — genuinely recursive (the
   historical test-local walkers descended ONE call-jaxpr level and
   missed jaxprs nested in deeper containers), with the compat helpers
   the other test files now route their pins through;
2. the BlockSpec checker — concrete index-map enumeration over the full
   grid;
3. the invariant registry — each seeded violation is caught BY NAME on a
   hand-built traced program (``audit_traced``: no module-level jit
   cache is touched, so mutations cannot leak between tests), and
   unknown engines fail closed;
4. the runtime gates — autotune never times a statically-invalid
   candidate; the serving warm path refuses an invalid plan; the CLI
   audits the matrix.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import analysis
from repro.analysis import blockspec_audit, invariants, jaxpr_audit
from repro.core import stencils
from repro.core.api import StencilPlan, StencilProblem

jax.config.update("jax_platform_name", "cpu")

F32 = jnp.float32


def _traced(fn, *avals):
    return jax.make_jaxpr(fn)(*avals)


def _audit(closed, plan, name="1d3p", shape=(256,), steps=6):
    prob = StencilProblem(name, shape)
    return analysis.audit_traced(closed, plan, prob.spec, shape,
                                 prob.dtype, steps)


# ---------------------------------------------------------------------------
# 1. the walker: full recursion depth (the historical shallow-walker bug)
# ---------------------------------------------------------------------------

def _nested_program():
    """mul buried 3 call-jaxprs deep: cond branch → pjit → scan body."""
    def inner(v):
        return lax.scan(lambda c, _: (c * 2.0, None), v, None, length=3)[0]

    def prog(v):
        return lax.cond(v.sum() > 0, jax.jit(inner), lambda u: u, v)

    return _traced(prog, jax.ShapeDtypeStruct((8,), F32))


def test_walker_reaches_nested_bodies():
    """The regression pin for the full-recursion fix: the mul lives in a
    scan body inside a jitted function inside a cond branch — 3 levels of
    call-jaxpr nesting — and the census must still count it."""
    closed = _nested_program()
    assert jaxpr_audit.max_call_depth(closed) >= 3
    c = jaxpr_audit.count_prims(closed)
    assert c["mul"] >= 1, dict(c)
    # loop membership survives the nesting: the mul is inside the scan
    muls = [s for s in jaxpr_audit.walk(closed) if s.prim == "mul"]
    assert muls and all(s.in_loop for s in muls)


def test_param_jaxprs_descends_dict_params():
    """Jaxprs hiding in dict-valued (and doubly-nested) params are found
    — exactly what the historical one-level walkers skipped."""
    closed = _traced(lambda v: v + 1.0, jax.ShapeDtypeStruct((4,), F32))

    class FakeEqn:
        params = {"deep": {"list": [("tag", closed)]}}

    subs = list(jaxpr_audit._param_jaxprs(FakeEqn()))
    assert subs == [closed.jaxpr]


def test_compat_helpers_match_historical_semantics():
    spec = stencils.make("1d3p")
    from repro.kernels import ops
    x = jax.ShapeDtypeStruct((256,), F32)
    closed = _traced(
        lambda v: ops._sweep_periodic_impl(spec, v, 6, 2, 8, 4, None,
                                           "fused", True),
        x)
    top, inside = jaxpr_audit.transpose_census(closed)
    assert inside == 0                        # the resident pin
    grids = jaxpr_audit.pallas_grids(closed)
    assert grids and all(isinstance(g, tuple) for g in grids)
    # enter_pallas=False counts the launch but not kernel-body prims;
    # enter_pallas=True strictly adds body prims on a pallas program
    shallow = jaxpr_audit.count_prims(closed)
    deep = jaxpr_audit.count_prims(closed, enter_pallas=True)
    assert shallow["pallas_call"] == deep["pallas_call"] == len(grids)
    assert sum(deep.values()) > sum(shallow.values())


# ---------------------------------------------------------------------------
# 2. BlockSpec enumeration
# ---------------------------------------------------------------------------

def _pallas_prog(in_map, out_map, grid=4, nblocks=4, blk=8, aliases=None):
    from jax.experimental import pallas as pl

    def kern(t_ref, o_ref):
        o_ref[...] = t_ref[...]

    kw = {}
    if aliases:
        kw["input_output_aliases"] = aliases
    fn = functools.partial(
        pl.pallas_call, kern, grid=(grid,),
        in_specs=[pl.BlockSpec((1, blk), in_map)],
        out_specs=pl.BlockSpec((1, blk), out_map),
        out_shape=jax.ShapeDtypeStruct((nblocks, blk), F32),
        interpret=True, **kw)()
    return _traced(lambda v: fn(v), jax.ShapeDtypeStruct((nblocks, blk), F32))


def _kinds(closed):
    return {f.kind for f in blockspec_audit.audit_blockspecs(closed)}


def test_blockspec_clean_identity():
    closed = _pallas_prog(lambda j: (j, 0), lambda j: (j, 0))
    assert _kinds(closed) == set()


def test_blockspec_oob_read():
    closed = _pallas_prog(lambda j: (j + 1, 0), lambda j: (j, 0))
    assert "blockspec-oob-read" in _kinds(closed)


def test_blockspec_write_overlap_and_gap():
    """Seeded violation: every grid step writes block 0 — gaps plus
    revisits is the overlapping-output-map signature."""
    closed = _pallas_prog(lambda j: (j, 0), lambda j: (0, 0))
    kinds = _kinds(closed)
    assert "blockspec-write-overlap" in kinds
    assert "blockspec-coverage-gap" in kinds


def test_blockspec_full_coverage_revisits_not_flagged():
    """The wrapped-grid design: revisits WITH full coverage (final
    writer wins on the sequential grid) are facts, not violations."""
    spec = stencils.make("1d3p")
    from repro.kernels import stencil_kernels as sk
    closed = _traced(
        lambda t: sk.stencil1d_sweep_ttile(spec, t, 2, 1, interpret=True),
        jax.ShapeDtypeStruct((4, 4, 8), F32))
    assert _kinds(closed) == set()


def test_blockspec_donate_alias_hazard():
    """Aliased input re-reads block 0 at every step while the aliased
    output wrote it at step 0 — donated buffers observe clobbered data."""
    closed = _pallas_prog(lambda j: (0, 0), lambda j: (j, 0),
                          aliases={0: 0})
    assert "blockspec-donate-alias" in _kinds(closed)


# ---------------------------------------------------------------------------
# 3. seeded invariant violations, caught by name
# ---------------------------------------------------------------------------

RESIDENT_PLAN = StencilPlan(scheme="transpose", k=2, vl=8, m=4,
                            backend="pallas", sweep="resident")


def test_seeded_in_loop_transpose():
    """Seeded violation 1: a transpose inside the sweep loop of a
    program audited as resident."""
    def body(i, t):
        return jnp.swapaxes(t, 0, 2) * 1.0

    closed = _traced(lambda v: lax.fori_loop(0, 4, body, v),
                     jax.ShapeDtypeStruct((8, 4, 8), F32))
    rep = _audit(closed, RESIDENT_PLAN)
    assert "resident-in-loop-transpose" in rep.violation_names()


def test_seeded_in_loop_reshape():
    def body(i, t):
        return t.reshape(4, 8, 8).reshape(8, 4, 8) * 1.0

    closed = _traced(lambda v: lax.fori_loop(0, 4, body, v),
                     jax.ShapeDtypeStruct((8, 4, 8), F32))
    rep = _audit(closed, RESIDENT_PLAN)
    assert "resident-in-loop-reshape" in rep.violation_names()


def test_seeded_overlapping_output_blockspec():
    """Seeded violation 2: the overlapping output index map surfaces as
    a violation through the full audit_traced path."""
    closed = _pallas_prog(lambda j: (j, 0), lambda j: (0, 0))
    rep = _audit(closed, StencilPlan(backend="jnp", scheme="fused", k=1))
    assert "blockspec-write-overlap" in rep.violation_names()


def test_seeded_bf16_accumulation():
    """Seeded violation 3: a dot_general accumulating in bf16 — the mxu
    engine must pin f32/f64 via preferred_element_type."""
    def prog(v):
        return lax.dot_general(v, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.bfloat16)

    closed = _traced(prog, jax.ShapeDtypeStruct((8, 8), F32))
    plan = StencilPlan(backend="mxu", k=4)
    rep = _audit(closed, plan, steps=4)       # chunks=[(4,1)] → 1 dot ok
    names = rep.violation_names()
    assert "mxu-accum-dtype" in names
    assert "mxu-dot-count" not in names


def test_seeded_whole_tile_ppermute():
    """Seeded violation 4: the lead-axis ring ships a whole t0-row tile
    pad instead of the exact d·r-row strip."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh = jax.make_mesh((1,), ("d0",))

    def prog(v):
        def inner(t):
            return lax.ppermute(t, "d0", [(0, 0)])
        return shard_map(inner, mesh=mesh, in_specs=P(),
                         out_specs=P())(v)

    # strip rank = ndim+2 = 4 for 2d5p; shape[0]=4=t0 is the tile pad
    closed = _traced(prog, jax.ShapeDtypeStruct((4, 4, 4, 4), F32))
    plan = StencilPlan(scheme="transpose", k=2, vl=4, m=4, t0=4,
                      backend="distributed", sweep="resident",
                      decomp=(2, 1))
    rep = _audit(closed, plan, name="2d5p", shape=(32, 64), steps=6)
    names = rep.violation_names()
    assert "axis0-whole-tile-ppermute" in names
    # ...and the exact 2-row strip (d·r = 2·1) is nowhere to be found
    assert "axis0-strips-missing" in names


def test_seeded_serialized_claimed_as_overlap():
    """An overlap=True plan whose traced kernels all consume ring data
    is serialized, whatever the plan says."""
    n_dev = len(jax.devices())
    if n_dev < 4:
        pytest.skip(f"needs 4 devices, have {n_dev}")
    import warnings
    prob = StencilProblem("2d5p", (128, 64))
    base = dict(scheme="transpose", k=2, vl=4, m=4, t0=4,
                backend="distributed", sweep="resident", decomp=(4, 1))
    ser = StencilPlan(**base)
    ovl = StencilPlan(**base, overlap=True)
    x = jax.ShapeDtypeStruct(prob.shape, prob.dtype)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        closed = _traced(lambda v: prob.run(v, 6, ser), x)
    rep = analysis.audit_traced(closed, ovl, prob.spec, prob.shape,
                                prob.dtype, 6)
    assert "overlap-serialized" in rep.violation_names()


def test_unknown_engine_fails_closed():
    """Seeded violation 5: unrecognized plan axes short-circuit to the
    single fail-closed violation, whatever the program looks like."""
    closed = _traced(lambda v: v + 1.0, jax.ShapeDtypeStruct((8,), F32))
    rep = _audit(closed, StencilPlan(backend="quantum"))
    assert rep.violation_names() == ("unknown-engine",)
    rep2 = _audit(closed, StencilPlan(sweep="sideways"))
    assert rep2.violation_names() == ("unknown-engine",)


def test_trace_error_fails_closed():
    """A plan whose program won't even trace (vl·m doesn't divide the
    grid) is reported as a violation, never raised."""
    prob = StencilProblem("1d3p", (256,))
    bad = StencilPlan(scheme="transpose", k=2, vl=5, m=3,
                      backend="pallas", sweep="resident")
    rep = analysis.audit_plan(prob, bad, steps=4)
    assert rep.violation_names() == ("trace-error",)


# ---------------------------------------------------------------------------
# legitimate plans audit clean end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,shape,plan,steps", [
    ("1d3p", (256,), StencilPlan(), 7),
    ("1d3p", (256,), StencilPlan(scheme="transpose", k=2, vl=8, m=4,
                                 backend="pallas", sweep="resident"), 7),
    ("1d3p", (256,), StencilPlan(scheme="transpose", k=2, vl=8, m=4,
                                 backend="pallas", sweep="resident",
                                 ttile=2, remainder="native"), 8),
    ("1d3p", (256,), StencilPlan(scheme="transpose", k=2, vl=8, m=4,
                                 backend="pallas", sweep="roundtrip"), 6),
    ("1d3p", (256,), StencilPlan(scheme="transpose", k=2, vl=8, m=8,
                                 backend="mxu"), 7),
    ("2d5p", (16, 64), StencilPlan(scheme="transpose", k=2, vl=4, m=4,
                                   t0=4, backend="pallas",
                                   sweep="resident"), 6),
])
def test_legitimate_plans_audit_ok(name, shape, plan, steps):
    prob = StencilProblem(name, shape)
    rep = analysis.audit_plan(prob, plan, steps=steps)
    assert rep.ok, rep.summary() + " " + str(rep.violations)
    assert rep.facts is not None and rep.seconds > 0


# ---------------------------------------------------------------------------
# 4. the runtime gates
# ---------------------------------------------------------------------------

def test_autotune_never_times_invalid(monkeypatch, tmp_path):
    """THE wiring pin: a candidate the auditor rejects is pruned with
    its violation named and the timer NEVER sees it."""
    from repro.core import autotune
    prob = StencilProblem("1d3p", (256,))
    real_audit = analysis.audit_plan

    def fake_audit(problem, plan, steps=8):
        rep = real_audit(problem, plan, steps=steps)
        if plan.backend == "pallas":
            return dataclasses.replace(
                rep, violations=(invariants.Violation(
                    "seeded-test-violation", "pallas plans poisoned"),))
        return rep

    monkeypatch.setattr(analysis, "audit_plan", fake_audit)
    timed = []

    def timer(fn, plan):
        timed.append(plan)
        return 1.0

    res = autotune.tune(prob, steps=6,
                        cache_path=str(tmp_path / "cache.json"),
                        timer=timer, max_measure=6, force=True)
    assert res.n_pruned_static >= 1
    assert res.audit_seconds > 0
    pruned_plans = [p for p, _ in res.pruned]
    assert all(p.backend == "pallas" for p in pruned_plans)
    assert all(p.backend != "pallas" for p in timed)
    assert all(p not in timed for p in pruned_plans)
    assert all(names == ("seeded-test-violation",)
               for _, names in res.pruned)
    # the prune stats survive the persisted cache record
    rec = autotune.get_cache(str(tmp_path / "cache.json")).get(res.key)
    assert rec["n_pruned_static"] == res.n_pruned_static
    assert rec["pruned"][0]["violations"] == ["seeded-test-violation"]


def test_autotune_all_invalid_raises(monkeypatch, tmp_path):
    from repro.core import autotune
    prob = StencilProblem("1d3p", (256,))

    def all_bad(problem, plan, steps=8):
        return analysis.AuditReport(
            plan=plan, steps=steps, facts=None, blockspec=(),
            violations=(invariants.Violation("seeded", "all bad"),),
            seconds=0.0)

    monkeypatch.setattr(analysis, "audit_plan", all_bad)
    with pytest.raises(RuntimeError, match="statically invalid"):
        autotune.tune(prob, steps=6,
                      cache_path=str(tmp_path / "cache.json"),
                      timer=lambda fn, plan: 1.0, force=True)


def test_audit_gate_env_disable(monkeypatch, tmp_path):
    from repro.core import autotune
    prob = StencilProblem("1d3p", (256,))

    def boom(problem, plan, steps=8):
        raise AssertionError("audit must not run with REPRO_PLAN_AUDIT=0")

    monkeypatch.setattr(analysis, "audit_plan", boom)
    monkeypatch.setenv("REPRO_PLAN_AUDIT", "0")
    res = autotune.tune(prob, steps=6,
                        cache_path=str(tmp_path / "cache.json"),
                        timer=lambda fn, plan: 1.0, max_measure=2,
                        force=True)
    assert res.n_pruned_static == 0 and res.audit_seconds == 0.0


def test_serve_warm_fails_closed(monkeypatch, tmp_path):
    from repro.serve.engine import StencilService

    def all_bad(problem, plan, steps=8):
        return analysis.AuditReport(
            plan=plan, steps=steps, facts=None, blockspec=(),
            violations=(invariants.Violation(
                "seeded-warm-violation", "refused"),),
            seconds=0.0)

    monkeypatch.setattr(analysis, "audit_plan", all_bad)
    svc = StencilService(cache_path=str(tmp_path / "cache.json"))
    try:
        fut = svc.warm_async("1d3p", (256,), steps=6,
                             timer=lambda fn, plan: 1.0, max_measure=2)
        with pytest.raises(RuntimeError,
                           match="seeded-warm-violation"):
            fut.result(timeout=600)
    finally:
        svc.close()


def test_cli_smoke(tmp_path, capsys):
    from repro.analysis.__main__ import main
    out = tmp_path / "audit.json"
    rc = main(["--limit", "1", "--steps", "4", "--json", str(out)])
    assert rc == 0
    import json
    data = json.loads(out.read_text())
    assert data["n_bad"] == 0 and data["n_plans"] >= 4
    assert all(r["ok"] for r in data["rows"])
