"""Layout-resident Pallas sweep engine (`ops.stencil_sweep_periodic`).

Three contracts pin the engine:

  1. parity matrix — resident sweeps are BIT-IDENTICAL to the per-sweep
     wrap-pad/crop path (`ops.stencil_run_periodic` under `_chunked`'s
     remainder decomposition) and allclose to the f64 oracle, across
     stencil families × k × remainder policies × ragged step counts;
  2. data-movement — the whole-run jaxpr contains NO per-sweep pad/wrap
     copies (no pad/concatenate/slice outside the pallas kernel bodies)
     and exactly one layout round-trip, while the legacy path provably
     pays one wrap-pad + crop per sweep;
  3. `pick_tile` never walks the transpose block below the stencil halo —
     it falls back to a smaller vl or raises a ValueError naming the
     shape (regression for the `m < r` assert crash).
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.analysis import jaxpr_audit
from repro.core import layouts, stencils
from repro.core.api import StencilPlan, StencilProblem
from repro.kernels import ops
from repro.kernels import stencil_kernels as sk

SHAPES = {"1d3p": (128,), "2d5p": (8, 64), "3d7p": (4, 4, 64)}
TILES = {"1d3p": dict(vl=8, m=8), "2d5p": dict(vl=8, m=4, t0=4),
         "3d7p": dict(vl=8, m=4, t0=4)}


def _x(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _f64_oracle(name, x, steps):
    spec = stencils.make(name)
    out = np.asarray(x).astype(np.float64)
    for _ in range(steps):
        out = stencils.numpy_apply_once(spec, out)
    return out


def _plans(name, k, remainder):
    kw = TILES[name]
    base = StencilPlan(scheme="transpose", k=k, backend="pallas",
                      remainder=remainder, **kw)
    import dataclasses
    return (dataclasses.replace(base, sweep="resident"),
            dataclasses.replace(base, sweep="roundtrip"))


# ---------------------------------------------------------------------------
# 1. parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("remainder", ["fused", "native"])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("name", ["1d3p", "2d5p", "3d7p"])
def test_resident_parity_matrix(name, k, remainder):
    """resident == per-sweep bitwise; both ≈ f64 oracle — including a
    steps that k does not divide (the remainder runs INSIDE the fused
    resident program)."""
    prob = StencilProblem(name, SHAPES[name])
    x = _x(SHAPES[name], seed=3)
    resident, roundtrip = _plans(name, k, remainder)
    for steps in (k * 2, k * 2 + max(1, k - 1)):     # divisible + ragged
        got = np.asarray(prob.run(x, steps, resident))
        ref = np.asarray(prob.run(x, steps, roundtrip))
        np.testing.assert_array_equal(
            got, ref, err_msg=f"{name} k={k} steps={steps} {remainder}: "
            "resident != per-sweep (must be bit-identical)")
        want = _f64_oracle(name, x, steps)
        np.testing.assert_allclose(got, want.astype(np.float32),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("name,shape,kw", [
    ("1d5p", (320,), dict(vl=8, m=4)),
    ("2d9p", (16, 64), dict(vl=8, m=4, t0=4)),
    ("3d27p", (8, 6, 64), dict(vl=8, m=4, t0=2)),
])
def test_resident_box_and_high_order(name, shape, kw):
    """r=2 and box stencils through the ops driver."""
    spec = stencils.make(name)
    x = _x(shape, seed=4)
    got = ops.stencil_sweep_periodic(spec, x, 5, k=2, remainder="native",
                                     interpret=True, **kw)
    want = stencils.apply_steps(spec, x, 5, bc="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_resident_donate_smoke():
    """The donated driver computes the same answer (donation is a no-op
    on CPU; on TPU it lets XLA update in place)."""
    spec = stencils.make("1d3p")
    x = _x((256,), seed=5)
    plain = ops.stencil_sweep_periodic(spec, x, 4, k=2, interpret=True)
    donated = ops.stencil_sweep_periodic(spec, jnp.array(x), 4, k=2,
                                         interpret=True, donate=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(donated))


# ---------------------------------------------------------------------------
# kernel-level: the wrapped-grid sweep kernels vs the periodic oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 3, 4])
@pytest.mark.parametrize("name,vl,m,nb", [
    ("1d3p", 8, 8, 6), ("1d3p", 8, 4, 1), ("1d5p", 8, 4, 3),
])
def test_stencil1d_sweep_periodic_kernel(name, vl, m, nb, k):
    """Fully-periodic k-step sweep straight on the resident layout —
    including nb=1 and halo > one block (k·r > vl·m never arises here,
    but p ≥ nb does)."""
    spec = stencils.make(name)
    x = _x((vl * m * nb,), seed=1)
    t = layouts.to_transpose_layout(x, vl, m)
    got = layouts.from_transpose_layout(
        sk.stencil1d_sweep_periodic(spec, t, k, interpret=True), vl, m)
    want = stencils.apply_steps(spec, x, k, bc="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("name,shape,vl,m,t0", [
    ("2d5p", (16, 64), 8, 4, 4),
    ("2d5p", (4, 32), 8, 4, 2),        # p >= n0t regime
    ("3d7p", (8, 6, 64), 8, 4, 4),
])
def test_stencil_nd_sweep_periodic_kernel(name, shape, vl, m, t0, k):
    spec = stencils.make(name)
    x = _x(shape, seed=2)
    t = layouts.to_transpose_layout(x, vl, m)
    got = layouts.from_transpose_layout(
        sk.stencil_nd_sweep_periodic(spec, t, k, t0, interpret=True), vl, m)
    want = stencils.apply_steps(spec, x, k, bc="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# 2. data-movement: jaxpr inspection
# ---------------------------------------------------------------------------

# the shared recursive walker (repro.analysis.jaxpr_audit) replaced the
# historical test-local copy; the census semantics — descend control-flow
# bodies, count but do not enter pallas kernel bodies — are pinned there.
_COPY_PRIMS = jaxpr_audit.COPY_PRIMS


def _count_prims(closed: jcore.ClosedJaxpr) -> collections.Counter:
    return jaxpr_audit.count_prims(closed)


def test_resident_jaxpr_has_no_per_sweep_copies():
    """The acceptance contract: the whole-run resident program contains
    zero pad/wrap/crop copies and exactly one layout round-trip; the
    legacy path pays a wrap-pad (concatenate) + crop (slice) per sweep."""
    spec = stencils.make("1d3p")
    x = jnp.zeros((256,), jnp.float32)
    resident = jax.make_jaxpr(lambda v: ops._sweep_periodic_impl(
        spec, v, 8, 2, 8, 8, None, "fused", True))(x)
    c = _count_prims(resident)
    for prim in _COPY_PRIMS:
        assert c[prim] == 0, (prim, dict(c))
    # one round-trip total: transpose-in + untranspose kernels + ONE sweep
    # kernel inside the loop = 3 pallas_calls, regardless of steps
    assert c["pallas_call"] == 3, dict(c)

    # ...while one sweep of the legacy path wrap-pads and crops
    legacy = jax.make_jaxpr(lambda v: ops.stencil_multistep_periodic
                            .__wrapped__(spec, v, 2, 8, 8, None, True))(x)
    lc = _count_prims(legacy)
    assert lc["concatenate"] >= 1 and lc["slice"] >= 1, dict(lc)


def test_resident_jaxpr_nd_single_layout_roundtrip():
    """n-D: exactly one transpose-in and one transpose-out (the jnp
    layout transform), none inside the sweep loop, ragged steps
    included."""
    spec = stencils.make("2d5p")
    x = jnp.zeros((16, 128), jnp.float32)
    resident = jax.make_jaxpr(lambda v: ops._sweep_periodic_impl(
        spec, v, 7, 2, 8, 8, 4, "native", True))(x)
    c = _count_prims(resident)
    for prim in _COPY_PRIMS:
        assert c[prim] == 0, (prim, dict(c))
    assert c["transpose"] == 2, dict(c)      # to_layout + from_layout only
    assert c["reshape"] == 2, dict(c)


# ---------------------------------------------------------------------------
# temporal tiling: depth-ttile·k trapezoid launches vs the PR 3 resident path
# ---------------------------------------------------------------------------

def _ttile_assert(name, got, ref, msg):
    """1-D/2-D: the ttile regrouping is BIT-identical to the plain
    resident schedule (same kernel arithmetic, same order per point).
    3-D: XLA's FMA contraction varies with the kernel unroll depth — a
    depth-4 launch and two depth-2 launches already differ by ≤1 ulp on
    the PRE-EXISTING `stencil_nd_sweep_periodic` path (both are correct
    roundings, equidistant from the f64 oracle) — so 3-D pins to a few
    ulp instead."""
    if stencils.make(name).ndim < 3:
        np.testing.assert_array_equal(got, ref, err_msg=msg)
    else:
        np.testing.assert_allclose(got, ref, rtol=3e-7, atol=3e-7,
                                   err_msg=msg)


@pytest.mark.parametrize("remainder", ["fused", "native"])
@pytest.mark.parametrize("ttile", [2, 4])
@pytest.mark.parametrize("name", ["1d3p", "2d5p", "3d7p"])
def test_ttile_parity_vs_resident(name, ttile, remainder):
    """ttile>1 == the ttile=1 resident path (the PR 3 engine is the
    oracle) across divisible, ragged and sub-k step counts; both ≈ the
    f64 oracle."""
    import dataclasses
    prob = StencilProblem(name, SHAPES[name])
    x = _x(SHAPES[name], seed=7)
    base = StencilPlan(scheme="transpose", k=2, backend="pallas",
                       sweep="resident", remainder=remainder, **TILES[name])
    tiled = dataclasses.replace(base, ttile=ttile)
    for steps in (8, 11, 5):
        got = np.asarray(prob.run(x, steps, tiled))
        ref = np.asarray(prob.run(x, steps, base))
        _ttile_assert(name, got, ref,
                      f"{name} k=2 ttile={ttile} steps={steps} "
                      f"{remainder}: != resident ttile=1")
        want = _f64_oracle(name, x, steps)
        np.testing.assert_allclose(got, want.astype(np.float32),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("ttile", [2, 3])
@pytest.mark.parametrize("k", [1, 2])
def test_stencil1d_sweep_ttile_kernel_equals_deeper_periodic(k, ttile):
    """Kernel-level contract: ONE depth-k·ttile trapezoid launch is the
    same program as the depth-k·ttile periodic sweep — the ttile axis
    only regroups launches, it never changes the kernel math."""
    spec = stencils.make("1d3p")
    x = _x((8 * 8 * 4,), seed=8)
    t = layouts.to_transpose_layout(x, 8, 8)
    got = sk.stencil1d_sweep_ttile(spec, t, k, ttile, interpret=True)
    ref = sk.stencil1d_sweep_periodic(spec, t, k * ttile, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_stencil_nd_sweep_ttile_kernel_equals_deeper_periodic():
    spec = stencils.make("2d5p")
    x = _x((16, 64), seed=9)
    t = layouts.to_transpose_layout(x, 8, 4)
    got = sk.stencil_nd_sweep_ttile(spec, t, 2, 2, 4, interpret=True)
    ref = sk.stencil_nd_sweep_periodic(spec, t, 4, 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_ttile_jaxpr_roundtrips_flat_in_steps():
    """The acceptance contract of the tentpole: HBM round-trips per run
    do NOT grow with steps/ttile — the whole-run ttile program is still
    exactly 3 pallas_calls (transpose in + ONE loop-carried sweep kernel
    + transpose out) with zero pad/wrap/crop copies, for any step
    count."""
    spec = stencils.make("1d3p")
    x = jnp.zeros((256,), jnp.float32)
    counts = []
    for steps in (8, 32):
        closed = jax.make_jaxpr(lambda v, s=steps: ops._sweep_periodic_impl(
            spec, v, s, 2, 8, 8, None, "fused", True, 4))(x)
        c = _count_prims(closed)
        for prim in _COPY_PRIMS:
            assert c[prim] == 0, (steps, prim, dict(c))
        counts.append(c["pallas_call"])
    assert counts == [3, 3], counts


def test_run_rejects_ttile_on_non_resident_paths():
    """ttile>1 has no meaning on engines that round-trip every sweep —
    the dispatcher refuses instead of silently ignoring the field."""
    prob = StencilProblem("1d3p", (128,))
    x = _x((128,))
    for plan in (StencilPlan(scheme="transpose", k=2, vl=8, m=8,
                             backend="pallas", sweep="roundtrip", ttile=2),
                 StencilPlan(scheme="fused", k=2, ttile=2)):
        with pytest.raises(ValueError, match="ttile=2 requires a resident"):
            prob.run(x, 8, plan)


# ---------------------------------------------------------------------------
# 3. pick_tile regression
# ---------------------------------------------------------------------------

def test_pick_tile_falls_back_to_smaller_vl():
    """1d5p (r=2) on shape (8,): vl=8 only admits m=1 < r — used to trip
    `assert m >= spec.r`; now falls back to a smaller vl."""
    spec = stencils.make("1d5p")
    vl, m, t0 = ops.pick_tile(spec, (8,))
    assert vl * m and 8 % (vl * m) == 0
    assert m >= spec.r and vl >= spec.r
    # and the driver actually runs with the fallback tile
    x = _x((8,), seed=6)
    got = ops.stencil_sweep_periodic(spec, x, 3, k=2, interpret=True)
    want = stencils.apply_steps(spec, x, 3, bc="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pick_tile_raises_clear_error_naming_shape():
    spec = stencils.make("1d5p")
    with pytest.raises(ValueError, match=r"1d5p.*\(7,\)"):
        ops.pick_tile(spec, (7,))
    # a caller-pinned vl is never silently changed: infeasible → error
    with pytest.raises(ValueError, match="vl=8"):
        ops.pick_tile(spec, (8,), vl=8)


def test_pick_tile_nd_pipeline_tile_error_names_shape():
    """The n-D t0 leg follows the same contract: no divisor of n0 can
    hold the halo → ValueError, not a bare assert.  (Needs r=2 in n-D —
    not in the registry yet — so build a bare spec.)"""
    spec = stencils.StencilSpec("test2d5w", 2, 2, "star", ())
    with pytest.raises(ValueError, match=r"test2d5w.*\(11, 64\).*t0"):
        ops.pick_tile(spec, (11, 64))       # 11 prime: only t0=1 < r
    assert ops.pick_tile(spec, (12, 64))[2] >= 2


def test_pick_tile_unchanged_for_legal_shapes():
    """The fix must not disturb the tiles existing call sites get."""
    assert ops.pick_tile(stencils.make("1d3p"), (512,)) == (128, 2, None)
    assert ops.pick_tile(stencils.make("1d3p"), (256 * 8,)) == (128, 8, None)
    assert ops.pick_tile(stencils.make("2d5p"), (16, 64)) == (8, 8, 8)
    assert ops.pick_tile(stencils.make("1d5p"), (8,)) == (4, 2, None)


def test_pick_tile_native_vl_on_128_divisible_shapes():
    """Regression: the default-vl gate tested divisibility by 2·DEFAULT_VL,
    so extents divisible by 128 but not 256 — (384,), (128,) — silently
    dropped to vl=8 (sublane-granule vectors on a lane-native extent).
    The gate is DEFAULT_VL itself."""
    spec = stencils.make("1d3p")
    assert ops.pick_tile(spec, (384,)) == (128, 1, None)
    assert ops.pick_tile(spec, (768,)) == (128, 3, None)
    assert ops.pick_tile(spec, (128,)) == (128, 1, None)


# ---------------------------------------------------------------------------
# hypothesis: resident ≡ per-sweep, property-tested (skips without the dep
# WITHOUT skipping the rest of this module)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(steps=st.integers(1, 9), k=st.sampled_from([1, 2, 3, 4]),
           nb=st.sampled_from([1, 2, 3]), m=st.sampled_from([4, 5]),
           remainder=st.sampled_from(["fused", "native"]),
           seed=st.integers(0, 99))
    @settings(max_examples=25, deadline=None)
    def test_resident_bit_identical_to_per_sweep_property(steps, k, nb, m,
                                                          remainder, seed):
        """For arbitrary (steps, k, block shape, remainder, data): the
        resident engine's output is bit-identical to the per-sweep
        wrap-pad/crop path run through the same plan decomposition."""
        vl = 4
        prob = StencilProblem("1d3p", (vl * m * nb,))
        x = jnp.asarray(np.random.default_rng(seed)
                        .standard_normal(vl * m * nb), jnp.float32)
        kw = dict(scheme="transpose", k=k, vl=vl, m=m, backend="pallas",
                  remainder=remainder)
        got = np.asarray(prob.run(x, steps,
                                  StencilPlan(sweep="resident", **kw)))
        ref = np.asarray(prob.run(x, steps,
                                  StencilPlan(sweep="roundtrip", **kw)))
        np.testing.assert_array_equal(got, ref)
else:                                                 # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_resident_bit_identical_to_per_sweep_property():
        pass
