"""jit'd public wrappers around the Pallas stencil kernels.

Natural-layout in/out: the wrappers perform the local transpose-layout
round-trip (itself a Pallas kernel on the 1-D path — §3.5), pick TPU-native
tile parameters, and run sweeps of k-step pipelined updates.

Two periodic execution engines:

  * ``stencil_run_periodic`` — legacy per-sweep round-trip: every k-step
    sweep wrap-pads the pipelined axis, transposes, runs the kernel,
    untransposes and crops (4 full-domain copies per sweep);
  * ``stencil_sweep_periodic`` — layout-RESIDENT engine: one jitted
    program transposes in once, runs ALL steps (k-blocks + remainder)
    with the wrapped-periodic kernels, and untransposes once.  Bit-
    identical to the former, with the layout/pad traffic amortized over
    the whole run.  The distributed runtime
    (``distributed/multistep.make_run``) is the shard_map rendering of
    the same idea: per-shard transpose once per run, halo blocks
    exchanged in layout, programs cached per configuration like the
    twin-jit pair below.

On CPU hosts the kernels execute in interpret mode (validation); on TPU they
compile via Mosaic.  ``interpret=None`` auto-detects.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import layouts
from repro.core.stencils import StencilSpec
from repro.kernels import stencil_kernels as sk


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _fit_m(n_minor: int, vl: int, r: int, m: int | None) -> int | None:
    """Largest legal m <= the requested/default m for this vl, or None."""
    m = m or (sk.DEFAULT_M if n_minor % (vl * sk.DEFAULT_M) == 0 else
              max(r, n_minor // vl // 2 or 1))
    while m >= r and n_minor % (vl * m):
        m -= 1
    return m if m >= r else None


def pick_tile(spec: StencilSpec, shape, vl: int | None = None,
              m: int | None = None, t0: int | None = None):
    """TPU-native defaults: vl=128 lanes, m=8 sublanes, pipeline tile t0 from
    :func:`pipeline_tile`; shrink for small/test shapes while keeping
    divisibility.

    When no legal ``m >= spec.r`` exists for the (default) vl — e.g. a
    1d5p stencil on shape (8,), where vl=8 only leaves m=1 < r — the vl is
    halved until a legal (vl, m) appears (a caller-pinned vl is honored,
    never silently changed); if no vl >= spec.r admits one — or no n-D
    pipeline tile t0 >= r divides shape[0] — a ValueError names the shape
    instead of tripping an assert."""
    n_minor = shape[-1]
    r = spec.r
    vl_req = vl
    # any 128-divisible extent gets the native lane count (the historical
    # `% (DEFAULT_VL * 2)` test silently dropped shapes like (384,) —
    # divisible by 128 but not 256 — to vl=8, pessimizing every
    # auto-tiled candidate; regression-pinned in tests/test_resident_sweep)
    vl = vl or (sk.DEFAULT_VL if n_minor % sk.DEFAULT_VL == 0 else 8)
    fit = _fit_m(n_minor, vl, r, m)
    while fit is None and vl_req is None and vl // 2 >= max(r, 1):
        vl //= 2                      # auto-picked vl: fall back to smaller
        fit = _fit_m(n_minor, vl, r, m)
    if fit is None:
        raise ValueError(
            f"no legal Pallas tile for stencil {spec.name!r} on shape "
            f"{tuple(shape)}: need m >= r={r} with vl*m dividing "
            f"n_minor={n_minor}"
            + (f" at the requested vl={vl_req}" if vl_req else ""))
    m = fit
    if len(shape) == 1:
        return vl, m, None
    n0 = shape[0]
    if t0 is None:
        t0 = pipeline_tile(n0, math.prod(shape[1:]), r)
    else:
        while n0 % t0:
            t0 -= 1
    if t0 < r:
        raise ValueError(
            f"no legal pipeline tile for stencil {spec.name!r} on shape "
            f"{tuple(shape)}: need t0 >= r={r} dividing n0={n0}")
    return vl, m, t0


def pipeline_tile(n0: int, row: int, r: int) -> int:
    """Default n-D pipeline tile over ``n0`` rows of ``row`` elements:
    the largest ``t0 <= 8`` dividing ``n0`` whose tile stays within
    ``ND_TILE_ELEMS`` elements, or the smallest ``t0 >= r`` dividing it
    when every tile is larger (0 when none divides)."""
    legal = [t for t in range(min(8, n0), max(r, 1) - 1, -1)
             if n0 % t == 0]
    small = [t for t in legal if t * row <= sk.ND_TILE_ELEMS]
    return (small or legal[-1:] or [0])[0]


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 6))
def stencil_multistep(spec: StencilSpec, x: jax.Array, k: int,
                      vl: int | None = None, m: int | None = None,
                      t0: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Advance x by k time steps with the pipelined transpose-layout kernel.

    BC: dirichlet along axis 0 (1-D: the spatial axis), periodic elsewhere.
    """
    interpret = _auto_interpret(interpret)
    vl, m, t0 = pick_tile(spec, x.shape, vl, m, t0)
    if spec.ndim == 1:
        t = sk.block_transpose(x, vl, m, interpret=interpret)
        out = sk.stencil1d_multistep(spec, t, k, interpret=interpret)
        return sk.block_untranspose(out, vl, m, interpret=interpret)
    t = layouts.to_transpose_layout(x, vl, m)      # (n0, *mid, nb, m, vl)
    out = sk.stencil_nd_multistep(spec, t, k, t0, interpret=interpret)
    return layouts.from_transpose_layout(out, vl, m)


def stencil_run(spec: StencilSpec, x: jax.Array, steps: int, k: int = 2,
                vl: int | None = None, m: int | None = None,
                t0: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """steps must divide into k-step sweeps."""
    assert steps % k == 0, (steps, k)
    for _ in range(steps // k):
        x = stencil_multistep(spec, x, k, vl, m, t0, interpret)
    return x


# ---------------------------------------------------------------------------
# Periodic-BC wrappers — what `StencilProblem.run(backend="pallas")` calls.
#
# The pipelined kernels are dirichlet along the pipelined axis (axis 0; the
# blocked spatial axis itself in 1-D).  Fully-periodic semantics — the
# contract of the jnp schemes and the autotuner's oracle — are recovered
# with the halo trick the distributed runtime already uses: wrap-pad the
# pipelined axis by >= k*r, run the kernel, crop.  Anything the frozen
# (or unmasked) padded edge corrupts lies within k*r of it and is cropped;
# the interior is the exact periodic k-step update.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 6))
def stencil_multistep_periodic(spec: StencilSpec, x: jax.Array, k: int,
                               vl: int | None = None, m: int | None = None,
                               t0: int | None = None,
                               interpret: bool | None = None) -> jax.Array:
    """Advance x by k time steps, periodic BC on every axis."""
    interpret = _auto_interpret(interpret)
    vl, m, t0 = pick_tile(spec, x.shape, vl, m, t0)
    r = spec.r
    if spec.ndim == 1:
        blk = vl * m
        pad = sk.sweep_halo_blocks(r, k, blk) * blk   # whole blocks ⊇ k*r
        xp = jnp.pad(x, [(pad, pad)], mode="wrap")
        t = sk.block_transpose(xp, vl, m, interpret=interpret)
        out = sk.stencil1d_multistep(spec, t, k, interpret=interpret,
                                     edge_mask=False)
        flat = sk.block_untranspose(out, vl, m, interpret=interpret)
        return jax.lax.slice_in_dim(flat, pad, pad + x.shape[-1], axis=0)
    pad0 = sk.sweep_halo_blocks(r, k, t0) * t0  # whole pipeline tiles
    xp = jnp.pad(x, [(pad0, pad0)] + [(0, 0)] * (x.ndim - 1), mode="wrap")
    t = layouts.to_transpose_layout(xp, vl, m)
    out = sk.stencil_nd_multistep(spec, t, k, t0, interpret=interpret)
    flat = layouts.from_transpose_layout(out, vl, m)
    return jax.lax.slice_in_dim(flat, pad0, pad0 + x.shape[0], axis=0)


def stencil_run_periodic(spec: StencilSpec, x: jax.Array, steps: int,
                         k: int = 2, vl: int | None = None,
                         m: int | None = None, t0: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """steps must divide into k-step sweeps (remainder policy lives in
    ``StencilProblem._chunked``, which re-invokes this with k=rem)."""
    assert steps % k == 0, (steps, k)
    for _ in range(steps // k):
        x = stencil_multistep_periodic(spec, x, k, vl, m, t0, interpret)
    return x


# ---------------------------------------------------------------------------
# Layout-resident sweep engine — the fast path `StencilProblem.run`
# dispatches for plans with sweep="resident".
#
# One jitted program for the WHOLE run: transpose into layout once, advance
# all `steps` (main k-blocks and the steps % k remainder, under either
# remainder policy) with the wrapped-periodic sweep kernels — which read
# their halo blocks straight out of the resident array through the grid
# index maps, so no wrap-pad / crop copy ever materializes — and
# untranspose once.  The layout round-trip is paid once per run (§3.2/§3.5
# amortization), not once per sweep.
# ---------------------------------------------------------------------------

def _sweep_periodic_impl(spec: StencilSpec, x: jax.Array, steps: int,
                         k: int, vl: int | None, m: int | None,
                         t0: int | None, remainder: str,
                         interpret: bool | None,
                         ttile: int = 1) -> jax.Array:
    if remainder not in ("fused", "native"):
        raise ValueError(f"unknown remainder policy {remainder!r}")
    interpret = _auto_interpret(interpret)
    vl, m, t0 = pick_tile(spec, x.shape, vl, m, t0)
    if steps <= 0:
        return x
    # the shared (depth, n_launches) decomposition: ttile-grouped main
    # k-blocks, ungrouped k-block leftovers, then the remainder policy —
    # the same chunks the distributed runtime executes and the roofline
    # charges (core.api.sweep_schedule is the single source of truth)
    from repro.core.api import sweep_schedule
    chunks, _ = sweep_schedule(k, steps, remainder, ttile)
    if spec.ndim == 1:
        t = sk.block_transpose(x, vl, m, interpret=interpret)
        sweep = lambda v, kk, tt: sk.stencil1d_sweep_ttile(
            spec, v, kk, tt, interpret=interpret)
    else:
        t = layouts.to_transpose_layout(x, vl, m)
        sweep = lambda v, kk, tt: sk.stencil_nd_sweep_ttile(
            spec, v, kk, tt, t0, interpret=interpret)

    def sweeps(v, kk, tt, n):
        if n == 1:
            return sweep(v, kk, tt)
        return jax.lax.fori_loop(0, n, lambda _, u: sweep(u, kk, tt), v)

    for depth, n in chunks:
        # a depth-k·ttile chunk runs as the time-tiled kernel (one HBM
        # round-trip per ttile k-blocks); plain k-blocks and the
        # remainder ("native": one shorter k=rem pipelined sweep,
        # "fused": rem single-step sweeps) run at ttile=1 — either way
        # the array never leaves the transpose layout.
        kk, tt = (k, depth // k) if depth > k and depth % k == 0 \
            else (depth, 1)
        t = sweeps(t, kk, tt, n)
    if spec.ndim == 1:
        return sk.block_untranspose(t, vl, m, interpret=interpret)
    return layouts.from_transpose_layout(t, vl, m)


_sweep_jit = jax.jit(_sweep_periodic_impl,
                     static_argnums=(0, 2, 3, 4, 5, 6, 7, 8, 9))
# donated twin: XLA reuses x's buffer for the result (no double-buffering
# at the jit boundary).  The caller's x is INVALIDATED on donation-capable
# backends (TPU) — opt in only when the input is dead after the call
# (steady-state sweep loops, benchmarks); CPU ignores donation.
_sweep_jit_donated = jax.jit(_sweep_periodic_impl,
                             static_argnums=(0, 2, 3, 4, 5, 6, 7, 8, 9),
                             donate_argnums=(1,))


def stencil_sweep_periodic(spec: StencilSpec, x: jax.Array, steps: int,
                           k: int = 2, vl: int | None = None,
                           m: int | None = None, t0: int | None = None,
                           remainder: str = "fused",
                           interpret: bool | None = None,
                           donate: bool = False,
                           ttile: int = 1) -> jax.Array:
    """Advance ``x`` by ``steps`` periodic steps, layout-resident.

    Equivalent to ``stencil_run_periodic`` over the main k-blocks plus the
    ``steps % k`` remainder under ``remainder`` — bit-identical output —
    but as ONE program: one transpose in, one transpose out, zero
    wrap-pad/crop copies (the sweep kernels wrap their reads through the
    grid index maps instead).  ``ttile > 1`` additionally fuses every
    ``ttile`` consecutive k-blocks into one depth-``ttile·k`` trapezoid
    launch (``stencil{1d,_nd}_sweep_ttile``): one HBM round-trip of the
    grid per ``ttile·k`` steps instead of per ``k``, still bit-identical
    (Jacobi updates are per-point order-independent, so launch grouping
    cannot change any arithmetic).  ``donate=True`` additionally donates
    ``x`` to the program (in-place update on TPU; the caller must not
    reuse x)."""
    impl = _sweep_jit_donated if donate else _sweep_jit
    return impl(spec, x, steps, k, vl, m, t0, remainder, interpret, ttile)


# ---------------------------------------------------------------------------
# MXU matrixization engine — `StencilProblem.run(backend="mxu")`.
#
# Same resident shape as the engine above (ONE program: transpose in,
# all sweep_schedule chunks, untranspose), but each depth-d chunk is ONE
# `dot_general` against the precomputed banded operator A^d
# (core/matrixize.py; A^d by repeated squaring at trace time).  The
# engine is jnp-level — XLA lowers the dot_general straight onto the
# MXU on TPU, and on CPU it runs native (no interpret-mode penalty), so
# the f64-oracle conformance matrix exercises the real engine.
# ---------------------------------------------------------------------------

def _sweep_mxu_impl(spec: StencilSpec, x: jax.Array, steps: int,
                    k: int, vl: int | None, m: int | None,
                    remainder: str, ttile: int = 1) -> jax.Array:
    if remainder not in ("fused", "native"):
        raise ValueError(f"unknown remainder policy {remainder!r}")
    vl, m, _ = pick_tile(spec, x.shape, vl, m)
    if steps <= 0:
        return x
    from repro.core.api import sweep_schedule
    chunks, _ = sweep_schedule(k, steps, remainder, ttile)
    t = layouts.to_transpose_layout(x, vl, m)   # (lead…, nb, m, vl)
    sweep = sk.stencil1d_sweep_mxu if spec.ndim == 1 \
        else sk.stencil_nd_sweep_mxu
    for depth, n in chunks:
        # one dot_general per launch: the depth-d operator advances d
        # steps in a single contraction (matrixize.operator is
        # lru-cached, so each distinct depth builds its A^d band once).
        if n == 1:
            t = sweep(spec, t, depth)
        else:
            t = jax.lax.fori_loop(
                0, n, lambda _, u: sweep(spec, u, depth), t)
    return layouts.from_transpose_layout(t, vl, m)


_mxu_jit = jax.jit(_sweep_mxu_impl, static_argnums=(0, 2, 3, 4, 5, 6, 7))
_mxu_jit_donated = jax.jit(_sweep_mxu_impl,
                           static_argnums=(0, 2, 3, 4, 5, 6, 7),
                           donate_argnums=(1,))


def stencil_sweep_mxu(spec: StencilSpec, x: jax.Array, steps: int,
                      k: int = 2, vl: int | None = None,
                      m: int | None = None, remainder: str = "fused",
                      donate: bool = False, ttile: int = 1) -> jax.Array:
    """Advance ``x`` by ``steps`` periodic steps on the MXU engine.

    Same (steps, k, remainder, ttile) decomposition as
    :func:`stencil_sweep_periodic` — ``sweep_schedule`` is the single
    source of truth — but every depth-``d`` chunk executes as ONE
    ``dot_general`` against the banded operator ``A^d``.  Matches the
    f64 oracle to accumulation-dtype tolerance (NOT bit-identical to
    the lane-shift engines: the matmul reassociates the tap sum)."""
    impl = _mxu_jit_donated if donate else _mxu_jit
    return impl(spec, x, steps, k, vl, m, remainder, ttile)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def stencil_onestep_naive(spec: StencilSpec, x: jax.Array,
                          vl: int = 8, interpret: bool | None = None):
    return sk.stencil1d_naive_onestep(spec, x, vl,
                                      interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def stencil_onestep_transpose(spec: StencilSpec, x: jax.Array,
                              vl: int = 8, m: int | None = None,
                              interpret: bool | None = None):
    interpret = _auto_interpret(interpret)
    m = m or vl
    t = layouts.to_transpose_layout(x, vl, m)
    out = sk.stencil1d_transpose_onestep(spec, t, interpret=interpret)
    return layouts.from_transpose_layout(out, vl, m)
