"""Banded-operator matrixization (core/matrixize.py) — the mxu engine.

Covers the tentpole contracts of the matrixization scheme:

  * the band algebra is EXACT: the one-step band reproduces
    ``stencils.numpy_apply_once`` in pure float64, and the depth-d
    operator built by repeated squaring equals d applications — checked
    against a pure-numpy oracle, independent of jnp/XLA;
  * ``band_power`` == repeated ``band_mul``; structurally-zero offset
    matrices are pruned, bounding ``block_reach`` by the ghost blocks
    the distributed codec actually exchanges;
  * ``apply_banded`` (the one-dot_general application) matches the f64
    oracle through the jax driver ``ops.stencil_sweep_mxu`` across step
    counts, remainder policies and temporal tiles;
  * halo-extended application (the distributed rendering) equals the
    periodic roll rendering on wrap-filled ghosts;
  * the jaxpr pin: A^d is built at TRACE time — the jitted program
    contains exactly ONE ``dot_general`` per sweep chunk and zero
    operator-construction matmuls;
  * ``mxu_plan_legal`` gates dtype, lane divisibility, band-vs-tile
    reach and the operator-size budget, all construction-free.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.analysis import jaxpr_audit
from repro.core import autotune, layouts, matrixize, stencils
from repro.kernels import ops
from repro.kernels import stencil_kernels as sk

NAMES = ["1d3p", "1d5p", "2d5p", "2d9p", "3d7p", "heat2d"]
SHAPES = {1: (128,), 2: (8, 64), 3: (4, 4, 64)}


# ---------------------------------------------------------------------------
# pure-numpy float64 rendering of the layout + banded application
# ---------------------------------------------------------------------------

def _np_layout(x: np.ndarray, vl: int, m: int) -> np.ndarray:
    """float64 twin of ``layouts.to_transpose_layout`` (jnp would downcast
    without x64): natural in-block index scattered by ``layout_perm``."""
    B = vl * m
    nat = x.reshape(x.shape[:-1] + (x.shape[-1] // B, B))
    lay = np.empty_like(nat)
    lay[..., matrixize.layout_perm(vl, m)] = nat
    return lay.reshape(nat.shape[:-1] + (m, vl))


def _np_apply_banded(op: matrixize.BandedOperator,
                     t: np.ndarray) -> np.ndarray:
    """Periodic float64 oracle of ``apply_banded`` (same gather
    convention: offset +o reads the neighbor at +o via roll by -o)."""
    tb = t.reshape(t.shape[:-2] + (op.B,))
    nd = tb.ndim
    nlead = op.ndim - 1
    out = np.zeros_like(tb)
    for kidx, off in enumerate(op.offsets):
        s = tb
        for a, o in enumerate(off[:-1]):
            s = np.roll(s, -o, axis=nd - 2 - nlead + a)
        s = np.roll(s, -off[-1], axis=-2)
        out = out + s @ op.table[kidx * op.B:(kidx + 1) * op.B]
    return out.reshape(t.shape)


def _rand64(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape)


# ---------------------------------------------------------------------------
# exactness of the band algebra (float64, no jnp)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("name", NAMES)
def test_operator_matches_numpy_oracle_exactly(name, depth):
    """A^depth applied to the layout == depth steps of the reference
    ``numpy_apply_once``, in pure float64 — the matrixization is the
    same linear map, not an approximation."""
    spec = stencils.make(name)
    vl, m = 4, 4
    x = _rand64(SHAPES[spec.ndim])
    want = x
    for _ in range(depth):
        want = stencils.numpy_apply_once(spec, want)
    op = matrixize.operator(spec, vl, m, depth)
    got = _np_apply_banded(op, _np_layout(x, vl, m))
    np.testing.assert_allclose(got, _np_layout(want, vl, m),
                               rtol=1e-12, atol=1e-12)


def test_layout_twin_matches_layouts_module():
    x = np.arange(64, dtype=np.float64)
    ours = _np_layout(x, 4, 4)
    theirs = np.asarray(layouts.to_transpose_layout(
        jnp.asarray(x, jnp.float32), 4, 4))
    np.testing.assert_array_equal(ours.astype(np.float32), theirs)


def test_band_power_equals_repeated_mul():
    spec = stencils.make("1d5p")
    band = matrixize.one_step_band(spec, 4, 4)
    seq = band
    for d in range(2, 6):
        seq = matrixize.band_mul(seq, band)
        pw = matrixize.band_power(band, d)
        assert set(pw) <= set(seq)
        for off, mat in pw.items():
            np.testing.assert_allclose(mat, seq[off], rtol=1e-12,
                                       atol=1e-14)
        # pruned offsets really are structural zeros
        for off in set(seq) - set(pw):
            assert not seq[off].any()


def test_block_reach_bounded_by_exchanged_ghosts():
    """The pruned band never reaches past the ghost blocks the
    distributed codec exchanges: block_reach <= ceil(depth·r / B)."""
    for name in NAMES:
        spec = stencils.make(name)
        for depth in (1, 2, 4):
            op = matrixize.operator(spec, 4, 4, depth)
            gb = sk.sweep_halo_blocks(spec.r, depth, op.B)
            assert op.block_reach() <= gb, (name, depth)
            for a in range(spec.ndim - 1):
                assert op.lead_reach(a) <= depth * spec.r


def test_operator_is_cached():
    spec = stencils.make("1d3p")
    assert matrixize.operator(spec, 8, 8, 2) is \
        matrixize.operator(spec, 8, 8, 2)


def test_operator_bytes_bound_is_upper_bound():
    for name in NAMES:
        spec = stencils.make(name)
        for depth in (1, 2, 3):
            op = matrixize.operator(spec, 4, 4, depth)
            actual = op.n_off * op.B * op.B * 4
            assert actual <= matrixize.operator_bytes_bound(
                spec, 4, 4, depth), (name, depth)


def test_accum_dtype_rules():
    assert matrixize.accum_dtype(jnp.bfloat16) == jnp.float32
    assert matrixize.accum_dtype(jnp.float32) == jnp.float32
    assert matrixize.accum_dtype(jnp.float64) == jnp.float64


# ---------------------------------------------------------------------------
# jax application: kernels, halo rendering, driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_sweep_mxu_kernels_match_oracle(name):
    spec = stencils.make(name)
    x = jnp.asarray(_rand64(SHAPES[spec.ndim], seed=1), jnp.float32)
    t = layouts.to_transpose_layout(x, 4, 4)
    fn = sk.stencil1d_sweep_mxu if spec.ndim == 1 else sk.stencil_nd_sweep_mxu
    got = layouts.from_transpose_layout(fn(spec, t, 2), 4, 4)
    want = stencils.apply_steps(spec, x, 2, bc="periodic")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)


def test_halo_rendering_equals_periodic_on_wrapped_ghosts():
    """Ghost-extended application (the distributed path) == the periodic
    roll rendering when the ghosts hold the periodic wrap — the single
    contract the shard codec relies on."""
    spec = stencils.make("1d5p")
    vl = m = 4
    depth = 2
    x = jnp.asarray(_rand64((128,), seed=2), jnp.float32)
    t = layouts.to_transpose_layout(x, vl, m)
    per = sk.stencil1d_sweep_mxu(spec, t, depth)
    gb = sk.sweep_halo_blocks(spec.r, depth, vl * m)
    ext = jnp.concatenate([t[-gb:], t, t[:gb]], axis=0)
    hal = sk.stencil1d_sweep_mxu_halo(spec, ext, depth, gb)
    assert hal.shape == per.shape      # interior only — no crop needed
    np.testing.assert_allclose(np.asarray(hal), np.asarray(per),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("remainder", ["fused", "native"])
@pytest.mark.parametrize("steps,k,ttile", [(7, 2, 1), (5, 4, 1), (8, 2, 2)])
def test_driver_matches_f64_oracle(steps, k, ttile, remainder):
    spec = stencils.make("1d3p")
    x64 = _rand64((128,), seed=3)
    x = jnp.asarray(x64, jnp.float32)
    want = np.asarray(x, np.float64)
    for _ in range(steps):
        want = stencils.numpy_apply_once(spec, want)
    got = ops.stencil_sweep_mxu(spec, x, steps, k=k, vl=8, m=8,
                                remainder=remainder, ttile=ttile)
    np.testing.assert_allclose(np.asarray(got), want.astype(np.float32),
                               rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# jaxpr pin: one dot_general per sweep chunk, zero operator matmuls
# ---------------------------------------------------------------------------

# shared recursive walker; enter_pallas=True matches the historical
# local copy (the mxu census descends kernel bodies too)
def _count_prims(closed: jcore.ClosedJaxpr) -> collections.Counter:
    return jaxpr_audit.count_prims(closed, enter_pallas=True)


@pytest.mark.parametrize("steps,k,remainder,ttile", [
    (7, 2, "fused", 1),       # chunks: (2, 3), (1, 1)
    (7, 2, "native", 1),      # chunks: (2, 3), (1, 1)
    (11, 4, "native", 1),     # chunks: (4, 2), (3, 1)
    (8, 2, "fused", 2),       # chunks: (4, 2)
])
def test_jaxpr_one_dot_general_per_chunk(steps, k, remainder, ttile):
    """The acceptance pin: A^d is built by repeated squaring at TRACE
    time (numpy), so the traced program contains exactly one
    ``dot_general`` per sweep chunk — were the power built inside the
    program, O(log d) extra operator-sized matmuls would appear here."""
    from repro.core.api import sweep_schedule
    spec = stencils.make("1d3p")
    x = jnp.zeros((128,), jnp.float32)
    chunks, _ = sweep_schedule(k, steps, remainder, ttile)
    closed = jax.make_jaxpr(
        lambda v: ops._sweep_mxu_impl(spec, v, steps, k, 8, 8,
                                      remainder, ttile))(x)
    c = _count_prims(closed)
    assert c["dot_general"] == len(chunks), (dict(c), chunks)


@pytest.mark.parametrize("dtype,precision", [
    (jnp.float32, jax.lax.Precision.HIGHEST),
    (jnp.bfloat16, None),
])
def test_jaxpr_dot_general_precision(dtype, precision):
    """f32 contractions ask for HIGHEST precision — the TPU's default
    runs them as bf16 passes — while bf16 keeps the default, with f32
    accumulation."""
    spec = stencils.make("2d5p")
    x = jnp.zeros((16, 64), dtype)
    closed = jax.make_jaxpr(
        lambda v: ops._sweep_mxu_impl(spec, v, 4, 2, 8, 8, "fused", 1))(x)
    dots = [s.eqn for s in jaxpr_audit.walk(closed, enter_pallas=True)
            if s.prim == "dot_general"]
    assert dots
    for eqn in dots:
        got = eqn.params["precision"]
        if precision is None:
            assert got is None or all(
                p in (None, jax.lax.Precision.DEFAULT) for p in got), got
        else:
            assert got == (precision, precision), got
        assert eqn.params["preferred_element_type"] == jnp.float32


# ---------------------------------------------------------------------------
# legality gate
# ---------------------------------------------------------------------------

def test_mxu_plan_legal_gates():
    spec = stencils.make("1d3p")
    legal = autotune.mxu_plan_legal
    assert legal(spec, (128,), 8, 8)
    assert legal(spec, (128,), 8, 8, dtype=jnp.bfloat16)
    # unknown dtype fails closed
    assert not legal(spec, (128,), 8, 8, dtype=jnp.int32)
    # minor extent must tile into (vl, m) lane blocks
    assert not legal(spec, (100,), 8, 8)
    # band must fit the exchanged ghost reach: depth·r <= vl·m
    assert legal(spec, (128,), 4, 4, k=16)
    assert not legal(spec, (128,), 4, 4, k=17)
    # operator-size budget (construction-free): B=1024 → ~12 MiB > 2 MiB
    assert matrixize.operator_bytes_bound(spec, 128, 8, 1) > \
        matrixize.OPERATOR_BUDGET
    assert not legal(spec, (2048,), 128, 8)


def test_mxu_plan_legal_distributed():
    spec = stencils.make("2d5p")
    legal = autotune.mxu_plan_legal
    assert legal(spec, (32, 64), 4, 4, decomp=(8, 1), n_devices=8)
    assert legal(spec, (32, 64), 4, 4, decomp=(2, 4), n_devices=8)
    # shard divisibility and device-count matching
    assert not legal(spec, (30, 64), 4, 4, decomp=(8, 1), n_devices=8)
    assert not legal(spec, (32, 64), 4, 4, decomp=(4, 1), n_devices=8)
    # decomposed local extent must hold the halo
    assert not legal(spec, (32, 64), 4, 4, decomp=(8, 1), n_devices=8,
                     k=5)


def test_mxu_candidates_enumerated_and_gated():
    spec = stencils.make("1d3p")
    cands = autotune.candidate_plans(spec, (512,), backend="mxu")
    assert cands and all(p.backend == "mxu" for p in cands)
    assert all(autotune.mxu_plan_legal(
        spec, (512,), p.vl, p.m, k=p.k, remainder=p.remainder,
        ttile=p.ttile, decomp=p.decomp) for p in cands)
    # the auto pool carries them too
    pool = autotune.candidate_plans(spec, (512,), steps=8)
    assert any(p.backend == "mxu" for p in pool)
