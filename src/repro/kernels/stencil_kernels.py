"""Pallas TPU kernels: transpose-layout + k-step unroll-and-jam stencils.

TPU rendering of the paper (see DESIGN.md §2):

  * vector  = one 128-lane row; VREG tile = (8, 128); VMEM tile = BlockSpec.
  * transpose layout: the unit-stride spatial dim is blocked into
    (nb, m, vl=128) with the local (vl × m) transpose of core/layouts.py —
    a +1 spatial shift becomes a second-minor row shift (free renaming /
    cheap sublane shift) instead of a 128-lane cross-lane roll.  Only the
    2r boundary rows per vector set need a lane-carry (blend + permute),
    built by ``vectorize.extend_vs``.
  * k-step unroll-and-jam: the Pallas grid is sequential on a TensorCore,
    so VMEM scratch persists across grid steps — the window of k live
    vector sets + the ``vrl`` carries of Algorithm 1 live in scratch.  Each
    grid step loads ONE block, stores ONE fully-updated block, and performs
    k block updates: HBM traffic is 1 read + 1 write per k time steps
    (arithmetic intensity ↑ k×, the paper's §3.3 claim, at VMEM scale).
  * multidimensional: the pipeline runs along the outermost spatial axis
    (y for 2-D, z for 3-D); inner spatial dims stay VMEM-resident per grid
    step, so their halos are internal (rolls on major axes); the
    unit-stride dim uses the transpose layout.  BC: dirichlet along the
    pipelined axis, periodic elsewhere (kernels' oracle in kernels/ref.py).
    Fully-periodic semantics — what ``StencilProblem.run`` and the
    autotuner's unified pool require — come in two renderings:

      - legacy round-trip (``kernels/ops.stencil_{multistep,run}_periodic``):
        wrap-pad the pipelined axis by >= k*r (whole blocks / pipeline
        tiles) in the natural layout, transpose, run the kernel, untranspose,
        crop — one full-domain pad copy and one layout round-trip per sweep;
      - layout-RESIDENT sweep (``stencil{1d,_nd}_sweep_periodic`` below, the
        fast path): the pallas grid itself runs over a *virtual* padded
        domain of ``nbp = nb + 2p`` blocks (``p = ceil(k*r / block)``); the
        input BlockSpec index map wraps ``(j - p) mod nb`` — the same
        periodic-carry trick ``extend_vs`` plays on the lane axis, lifted to
        the block/tile axis — so the halo blocks are *read* straight out of
        the resident (nb, m, vl) array and no padded copy ever materializes.
        Output writes land at ``(bp - p) mod nb``: the p corrupted head
        blocks (garbage within k·r of the virtual dirichlet edge) are
        overwritten by their correct versions later in the same grid, and
        the p corrupted tail writes are suppressed in-kernel (the out index
        freezes on the last correct block, whose buffer revisits untouched
        until the final flush).  One kernel launch per sweep, zero copies —
        fully periodic on every axis, bit-identical to the pad/crop path.

    ``kernels/ops.stencil_sweep_periodic`` chains these sweeps (main
    k-blocks AND the steps % k remainder policy) inside ONE jitted program
    that transposes in once and untransposes once per *run* — the paper's
    §3.2/§3.5 claim that the layout cost is paid once per tile lifetime,
    honored across the whole time loop.  The raw multistep kernels stay
    dirichlet so the distributed halo runtime (edge_mask=False +
    halo-block exchange) keeps its contract — and the shard-RESIDENT
    distributed engine (distributed/multistep.py) feeds these same
    ``sweep_periodic`` kernels a halo-extended resident shard: the ghost
    ring arrives as whole layout blocks via ppermute, the wrapped reads
    make no further copy, and the wrap corruption lands inside the
    cropped ghost blocks.

Grid-step uniform formulation (boot folded into the steady loop): at grid
step j, window position i holds block ``j-k+i`` at time ``k-1-i``; blocks
outside [0, nb) are masked; output block ``max(j-k, 0)`` is (re)written
every step — the final (j = b+k) write is the completed block, and on TPU
the out buffer only flushes when its block index changes, so intermediate
writes never touch HBM.

The dirichlet ring masks are hoisted: the resident/periodic path builds
no masks at all, and the dirichlet path builds each iota comparison once
per kernel invocation (outside the k-unroll loop), not once per unroll
position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.stencils import StencilSpec
from repro.core.vectorize import extend_vs

DEFAULT_VL = 128   # TPU lane count
DEFAULT_M = 8      # TPU sublane count (f32)
#: VMEM a kernel may claim: v5e has 128 MiB per core, and Mosaic keeps
#: the rest for its own scratch.  Plans whose ``sweep_vmem_bytes`` exceed it
#: are illegal (``core.autotune.pallas_plan_legal``).
VMEM_LIMIT_BYTES = 100 << 20
SCOPED_VMEM_DEFAULT = 16 << 20   # Mosaic's limit when a kernel sets none
#: largest pipeline tile (t0·mid·minor elements) the n-D tilers pick when
#: a smaller t0 is legal: the kernel body unrolls over the whole tile, so
#: Mosaic's VMEM use and compile time grow with it
ND_TILE_ELEMS = 1 << 16
#: tags on every kernel launch (the custom call's ``kernel_metadata``, in
#: the HLO and the device trace): a stencil sweep, or a layout transform
SWEEP = {"repro": "sweep"}
LAYOUT = {"repro": "layout"}


def _ring_masks_np(vl: int, m: int, r: int):
    """(m, vl) masks of the first/last r elements of a block (see
    core.unroll_jam._ring_masks)."""
    fm = np.zeros((m, vl), bool)
    lm = np.zeros((m, vl), bool)
    for e in range(r):
        fm[e % m, e // m] = True
        le = vl * m - 1 - e
        lm[le % m, le // m] = True
    return fm, lm


def _padded_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of an array: the minor dim pads to 128 lanes, the
    second-minor to the sublane tile (Mosaic gives a 1-row second-minor
    a 1-row tile)."""
    *lead, sub, lane = shape
    sub_tile = 1 if sub == 1 else 32 // itemsize
    return (int(np.prod(lead)) * -(-sub // sub_tile) * sub_tile
            * -(-lane // 128) * 128 * itemsize)


def sweep_vmem_bytes(spec: StencilSpec, block, depth: int,
                     itemsize: int) -> int:
    """Upper bound on the VMEM a depth-``depth`` sweep kernel launch needs
    for one ``block`` — (t0, *mid, nb, m, vl), or (1, m, vl) in 1-D: the
    in/out blocks double-buffered, the window and carry scratch, and the
    unrolled body's temporaries.  The last term, ``12 + 1.5·taps`` blocks
    per unrolled step, bounds what Mosaic reported for the 3-D stencils
    at 256³ and 512×256×1024 on v5e (t0 1-8, depth 1-4)."""
    blk = _padded_bytes(block, itemsize)
    row = blk // block[0]
    scratch = depth * (blk + spec.r * row)
    body = depth * blk * (12 + 3 * len(spec.taps) / 2)
    return int(4 * blk + scratch + body)


def _sweep_compiler_params(spec, block, depth, dtype
                           ) -> pltpu.CompilerParams:
    need = sweep_vmem_bytes(spec, block, depth, jnp.dtype(dtype).itemsize)
    return pltpu.CompilerParams(vmem_limit_bytes=min(
        VMEM_LIMIT_BYTES, max(SCOPED_VMEM_DEFAULT, need)))


def _lane_rotate(x: jax.Array, shift: int) -> jax.Array:
    """``jnp.roll(x, shift, axis=-1)`` as one TPU lane rotate.  Mosaic
    rotates 32-bit data only, so narrower dtypes rotate widened (exact)."""
    if x.dtype.itemsize >= 4:
        return pltpu.roll(x, shift, x.ndim - 1)
    return pltpu.roll(x.astype(jnp.float32), shift, x.ndim - 1
                      ).astype(x.dtype)


def _tap_sum_1d(spec: StencilSpec, ext: jax.Array, m: int) -> jax.Array:
    r = spec.r
    acc = None
    for off, c in spec.taps:
        sl = lax.slice_in_dim(ext, r + off[-1], r + off[-1] + m, axis=0)
        term = sl * jnp.asarray(c, ext.dtype)
        acc = term if acc is None else acc + term
    return acc


# ---------------------------------------------------------------------------
# 1-D: pipeline along the block axis (pure Algorithm 1).
# ---------------------------------------------------------------------------

def _kernel_1d(t_ref, o_ref, win_ref, vrl_ref, *, spec: StencilSpec,
               nb: int, m: int, vl: int, k: int, edge_mask: bool = True,
               write_stop: int | None = None):
    r = spec.r
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        win_ref[...] = jnp.zeros_like(win_ref)
        vrl_ref[...] = jnp.zeros_like(vrl_ref)

    if edge_mask:
        # ring masks built in-kernel (pallas cannot capture array consts;
        # jax raises "consts not supported in pallas_call"), hoisted here —
        # once per kernel invocation, outside the k-unroll loop, and not
        # built at all on the periodic/resident path (edge_mask=False):
        # element e of a block sits at (row e % m, lane e // m); with
        # r <= m the first r elements are lane 0 / rows < r, the last r
        # lane vl-1 / rows >= m-r (cf. _ring_masks_np, property-tested
        # against this closed form).
        rows = lax.broadcasted_iota(jnp.int32, (m, vl), 0)
        lanes = lax.broadcasted_iota(jnp.int32, (m, vl), 1)
        first_mask = (lanes == 0) & (rows < r)
        last_mask = (lanes == vl - 1) & (rows >= m - r)

    # Assemble = blend + lane rotate, 2 ops per boundary vector (Fig. 3):
    # the neighbor's carry lane is blended in where the rotate will wrap,
    # so no lane index or scatter is needed (Mosaic lowers neither).
    carry_lanes = lax.broadcasted_iota(jnp.int32, (r, vl), 1)
    from_left = carry_lanes == vl - 1
    from_right = carry_lanes == 0
    incoming = t_ref[0]                           # (m, vl)
    ws = [win_ref[i] for i in range(k)] + [incoming]
    new_vr = [None] * k
    for i in range(k - 1, -1, -1):                # paper's i = k..1
        b = j - (k - i)                           # block held at position i
        vs = ws[i]
        new_vr[i] = vs[m - r:, :]                 # preserve pre-update tail
        left_tail = vrl_ref[i]                    # left block tail, same time
        right_head = ws[i + 1][:r, :]             # right block, just updated
        left_rows = _lane_rotate(
            jnp.where(from_left, left_tail, vs[m - r:, :]), 1)
        right_rows = _lane_rotate(
            jnp.where(from_right, right_head, vs[:r, :]), vl - 1)
        ext = jnp.concatenate([left_rows, vs, right_rows], axis=0)
        new = _tap_sum_1d(spec, ext, m)
        keep = (b < 0) | (b >= nb)
        if edge_mask:   # dirichlet ring; False → caller crops halo blocks
            keep = keep | ((b == 0) & first_mask) | \
                ((b == nb - 1) & last_mask)
        ws[i] = jnp.where(keep, vs, new)
    if write_stop is None:
        o_ref[0] = ws[0]
    else:
        # wrapped-periodic mode: past write_stop the out index is frozen on
        # the last correct block — leave its buffer untouched so the final
        # flush rewrites correct data (see stencil1d_sweep_periodic).
        @pl.when(j < write_stop)
        def _write():
            o_ref[0] = ws[0]
    for i in range(k):
        win_ref[i] = ws[i + 1]
        vrl_ref[i] = new_vr[i]


def stencil1d_multistep(spec: StencilSpec, t: jax.Array, k: int,
                        *, interpret: bool,
                        edge_mask: bool = True) -> jax.Array:
    """t: (nb, m, vl) transpose-layout input → k-step update (dirichlet).

    edge_mask=False leaves the first/last blocks un-masked (garbage within
    k·r of the domain edge) — used by the distributed halo path, which
    exchanges whole halo blocks and crops them after the sweep."""
    nb, m, vl = t.shape
    r = spec.r
    assert r <= m and r <= vl
    kern = functools.partial(_kernel_1d, spec=spec, nb=nb, m=m, vl=vl, k=k,
                             edge_mask=edge_mask)
    return pl.pallas_call(
        kern,
        grid=(nb + k,),
        in_specs=[pl.BlockSpec((1, m, vl),
                               lambda j: (jnp.minimum(j, nb - 1), 0, 0))],
        out_specs=pl.BlockSpec((1, m, vl),
                               lambda j: (jnp.maximum(j - k, 0), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, m, vl), t.dtype),
        scratch_shapes=[pltpu.VMEM((k, m, vl), t.dtype),
                        pltpu.VMEM((k, r, vl), t.dtype)],
        compiler_params=_sweep_compiler_params(spec, (1, m, vl), k,
                                               t.dtype),
        interpret=interpret,
        metadata=SWEEP,
    )(t)


def sweep_halo_blocks(r: int, k: int, block: int) -> int:
    """Blocks (or pipeline tiles) of the virtual halo: the smallest whole
    number of ``block``-sized units covering the k·r-element corruption a
    k-step sweep admits at a dirichlet edge."""
    return -(-(k * r) // block)


def wrapped_sweep_index_maps(nblocks: int, pad: int, depth: int):
    """The wrapped-grid (input, output) index maps of a depth-``depth``
    periodic sweep launch over ``nblocks`` resident blocks with a
    ``pad``-block virtual halo per side — shared by the 1-D and n-D
    resident sweep kernels, and the construction
    :mod:`repro.analysis.blockspec_audit` enumerates concretely:

    * reads wrap: ``(min(j, nblocks + 2·pad − 1) − pad) mod nblocks``
      stays inside ``[0, nblocks)`` for every grid step by construction,
      so the virtual halo blocks come straight from the resident array
      (the blockspec auditor's no-OOB-read guarantee);
    * writes trail by ``depth`` grid steps and clamp-then-wrap:
      ``(clip(j − depth, 0, nblocks + pad − 1) − pad) mod nblocks`` —
      the ``pad`` corrupted head blocks land first and are re-written
      correctly later in the same sequential grid (final writer wins:
      full coverage WITH revisits, which the auditor recognizes as the
      design rather than a race), and the corrupted tail writes freeze
      on the last correct block, suppressed in-kernel past
      ``write_stop``.

    Returns closures over grid index ``j`` producing the leading
    (pipelined) block coordinate as a 1-tuple; callers append their
    trailing zero coordinates."""
    nbp = nblocks + 2 * pad

    def in_map(j):
        return ((jnp.minimum(j, nbp - 1) - pad) % nblocks,)

    def out_map(j):
        return ((jnp.clip(j - depth, 0, nblocks + pad - 1) - pad)
                % nblocks,)

    return in_map, out_map


def stencil1d_sweep_halo(spec: StencilSpec, t: jax.Array, k: int,
                         halo: int, *, interpret: bool) -> jax.Array:
    """One k-step sweep on a halo-EXTENDED layout-resident (nb, m, vl)
    shard — the distributed engine's sweep kernel.

    ``halo`` is the valid ghost width (elements per side) the caller
    exchanged into the edge blocks; everything the un-masked edges
    corrupt lies within k·r <= ``halo`` of the extended edges, inside
    the ghost blocks the caller crops.  Unlike
    :func:`stencil1d_sweep_periodic` there is NO virtual wrap halo: the
    grid runs exactly ``nb + k`` steps instead of ``nb + 2p + k``
    (``p = sweep_halo_blocks(r, k, vl·m)``) — periodicity is the
    exchanged ghost blocks' job, not the index maps', so a small shard
    stops paying 2p redundant virtual-block updates per sweep."""
    assert halo >= k * spec.r, (halo, k, spec.r)
    return stencil1d_multistep(spec, t, k, interpret=interpret,
                               edge_mask=False)


def stencil_nd_sweep_halo(spec: StencilSpec, t: jax.Array, k: int, t0: int,
                          halo: int, *, interpret: bool
                          ) -> jax.Array:
    """n-D analogue of :func:`stencil1d_sweep_halo`: one k-step sweep on
    a shard whose pipelined axis 0 carries ``halo`` exchanged ghost rows
    per side (whole t0-row tiles).  Mid and minor axes stay periodic
    in-kernel over the (possibly ghost-extended) local extents — a
    decomposed mid/minor axis confines the wrap corruption to its own
    exchanged ghosts.  Grid: ``n0/t0 + k`` steps, no 2p virtual tiles."""
    assert halo >= k * spec.r and halo % t0 == 0, (halo, k, spec.r, t0)
    return stencil_nd_multistep(spec, t, k, t0, interpret=interpret,
                                edge_mask=False)


def stencil1d_sweep_ttile(spec: StencilSpec, t: jax.Array, k: int,
                          ttile: int = 1, *, interpret: bool
                          ) -> jax.Array:
    """``ttile`` fully-periodic k-step sweeps — ``depth = ttile·k`` time
    steps — in ONE wrapped-grid launch on the layout-RESIDENT (nb, m, vl)
    array: the trapezoid/diamond time-tile schedule over the pipelined
    block axis.  No pad copy, no layout round-trip, ONE HBM round-trip of
    the grid per ``ttile·k`` steps (vs one per ``k`` for the plain sweep).

    Each block advances all ``depth`` steps inside the VMEM scratch
    window before its halo dependence forces the next block touch: the
    window holds ``depth`` live blocks skewed in time (block ``j-depth+i``
    at time ``depth-1-i`` — the tile's slope), so the per-block compute is
    the full time tile and the redundant work lives in the ``2p`` virtual
    halo blocks (``p = ceil(depth·r / block)``) covering the slope.

    The grid runs over a virtual padded domain of ``nbp = nb + 2p``
    blocks.  Reads wrap through the input index map (``(j - p) mod nb``),
    so halo blocks come straight from the resident array; writes land at
    ``(bp - p) mod nb`` where the p corrupted head blocks are re-written
    correctly later in the same grid and the p corrupted tail writes are
    suppressed (out index frozen on the last correct block, kernel skips
    o_ref past ``write_stop``).  Because Jacobi updates are per-point and
    order-independent, a depth-``ttile·k`` launch is bit-identical to
    ``ttile`` successive k-step launches — the parity oracle the tests
    pin — and to wrap-pad + ``stencil1d_multistep(edge_mask=False)`` +
    crop."""
    nb, m, vl = t.shape
    r = spec.r
    assert r <= m and r <= vl
    depth = k * max(ttile, 1)
    p = sweep_halo_blocks(r, depth, vl * m)
    nbp = nb + 2 * p
    kern = functools.partial(_kernel_1d, spec=spec, nb=nbp, m=m, vl=vl,
                             k=depth, edge_mask=False,
                             write_stop=nb + p + depth)
    in_map, out_map = wrapped_sweep_index_maps(nb, p, depth)
    return pl.pallas_call(
        kern,
        grid=(nbp + depth,),
        in_specs=[pl.BlockSpec(
            (1, m, vl), lambda j: in_map(j) + (0, 0))],
        out_specs=pl.BlockSpec(
            (1, m, vl), lambda j: out_map(j) + (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, m, vl), t.dtype),
        scratch_shapes=[pltpu.VMEM((depth, m, vl), t.dtype),
                        pltpu.VMEM((depth, r, vl), t.dtype)],
        compiler_params=_sweep_compiler_params(spec, (1, m, vl), depth,
                                               t.dtype),
        interpret=interpret,
        metadata=SWEEP,
    )(t)


def stencil1d_sweep_periodic(spec: StencilSpec, t: jax.Array, k: int,
                             *, interpret: bool) -> jax.Array:
    """One fully-periodic k-step sweep on the layout-RESIDENT (nb, m, vl)
    array — the ``ttile=1`` slice of :func:`stencil1d_sweep_ttile` (see
    there for the wrapped-grid construction)."""
    return stencil1d_sweep_ttile(spec, t, k, 1, interpret=interpret)


# ---------------------------------------------------------------------------
# n-D (n = 2, 3): pipeline along axis 0; inner dims VMEM-resident.
# ---------------------------------------------------------------------------

def _kernel_nd(t_ref, o_ref, win_ref, vrl_ref, *, spec: StencilSpec,
               n0t: int, t0: int, k: int, edge_mask: bool = True,
               write_stop: int | None = None):
    """t_ref block: (t0, *mid, nb, m, vl); pipeline along axis 0."""
    r = spec.r
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        win_ref[...] = jnp.zeros_like(win_ref)
        vrl_ref[...] = jnp.zeros_like(vrl_ref)

    incoming = t_ref[...]
    m = incoming.shape[-2]
    ndim_mid = incoming.ndim - 4                  # spatial dims between 0 & x
    ws = [win_ref[i] for i in range(k)] + [incoming]
    new_vr = [None] * k
    if edge_mask:
        # dirichlet ring comparisons, hoisted out of the k-unroll loop and
        # skipped entirely on the periodic/resident path
        row_idx = lax.broadcasted_iota(
            jnp.int32, (t0,) + (1,) * (incoming.ndim - 1), 0)
        top_ring = row_idx < r
        bot_ring = row_idx >= t0 - r
    for i in range(k - 1, -1, -1):
        b = j - (k - i)
        tile = ws[i]
        new_vr[i] = tile[t0 - r:]
        up_rows = vrl_ref[i]                      # (r, *mid, nb, m, vl)
        down_rows = ws[i + 1][:r]
        ext0 = jnp.concatenate([up_rows, tile, down_rows], axis=0)
        extx = extend_vs(ext0, r)                 # lane-carry on x (periodic)
        acc = None
        for off, c in spec.taps:
            o0, ox = off[0], off[-1]
            sl = lax.slice_in_dim(extx, r + o0, r + o0 + t0, axis=0)
            for ax, o in enumerate(off[1:-1]):
                if o:
                    sl = jnp.roll(sl, -o, axis=1 + ax)   # periodic mid dims
            sl = lax.slice_in_dim(sl, r + ox, r + ox + m, axis=sl.ndim - 2)
            term = sl * jnp.asarray(c, tile.dtype)
            acc = term if acc is None else acc + term
        keep = (b < 0) | (b >= n0t)
        if edge_mask:
            # dirichlet ring along axis 0 on the global first/last tiles
            keep = keep | ((b == 0) & top_ring) | \
                ((b == n0t - 1) & bot_ring)
        ws[i] = jnp.where(keep, tile, acc)
    if write_stop is None:
        o_ref[...] = ws[0]
    else:
        @pl.when(j < write_stop)
        def _write():
            o_ref[...] = ws[0]
    for i in range(k):
        win_ref[i] = ws[i + 1]
        vrl_ref[i] = new_vr[i]


def stencil_nd_multistep(spec: StencilSpec, t: jax.Array, k: int, t0: int,
                         *, interpret: bool,
                         edge_mask: bool = True) -> jax.Array:
    """t: (n0, *mid, nb, m, vl) — transpose layout on the minor spatial dim.

    Pipelines k time steps along axis 0 in tiles of t0 rows.  BC: dirichlet
    along axis 0, periodic along every other axis.  ``edge_mask=False``
    leaves the first/last pipeline tiles un-masked (garbage within k·r of
    the axis-0 edges) — the distributed halo runtime's contract: it
    exchanges whole halo tiles and crops them after the sweep."""
    n0 = t.shape[0]
    r = spec.r
    assert n0 % t0 == 0 and t0 >= r, (n0, t0, r)
    n0t = n0 // t0
    assert spec.r <= t.shape[-2]
    block = (t0,) + t.shape[1:]
    nd = t.ndim
    kern = functools.partial(_kernel_nd, spec=spec, n0t=n0t, t0=t0, k=k,
                             edge_mask=edge_mask)
    zeros_tail = (0,) * (nd - 1)
    return pl.pallas_call(
        kern,
        grid=(n0t + k,),
        in_specs=[pl.BlockSpec(block,
                               lambda j: (jnp.minimum(j, n0t - 1),) + zeros_tail)],
        out_specs=pl.BlockSpec(block,
                               lambda j: (jnp.maximum(j - k, 0),) + zeros_tail),
        out_shape=jax.ShapeDtypeStruct(t.shape, t.dtype),
        scratch_shapes=[pltpu.VMEM((k,) + block, t.dtype),
                        pltpu.VMEM((k, r) + block[1:], t.dtype)],
        compiler_params=_sweep_compiler_params(spec, block, k, t.dtype),
        interpret=interpret,
        metadata=SWEEP,
    )(t)


def stencil_nd_sweep_ttile(spec: StencilSpec, t: jax.Array, k: int,
                           ttile: int, t0: int, *, interpret: bool
                           ) -> jax.Array:
    """``ttile`` fully-periodic k-step sweeps (``depth = ttile·k`` time
    steps) in ONE wrapped-grid launch on the layout-RESIDENT
    (n0, *mid, nb, m, vl) array — the n-D analogue of
    :func:`stencil1d_sweep_ttile`, time-tiling the pipeline-tile axis
    (axis 0) through the index maps instead of a wrap-pad copy.  Mid dims
    and the unit-stride dim are periodic in-kernel already (rolls +
    ``extend_vs`` lane carry), so the trapezoid slope only widens the
    axis-0 virtual halo: ``p = ceil(depth·r / t0)`` tiles per side, and
    every (t0 × mid × vl·m) tile advances the full ``depth`` steps in
    VMEM between HBM touches.  Bit-identical to ``ttile`` successive
    k-step launches (Jacobi updates are per-point order-independent)."""
    n0 = t.shape[0]
    r = spec.r
    assert n0 % t0 == 0 and t0 >= r, (n0, t0, r)
    assert r <= t.shape[-2]
    depth = k * max(ttile, 1)
    n0t = n0 // t0
    p = sweep_halo_blocks(r, depth, t0)
    n0tp = n0t + 2 * p
    block = (t0,) + t.shape[1:]
    nd = t.ndim
    kern = functools.partial(_kernel_nd, spec=spec, n0t=n0tp, t0=t0,
                             k=depth, edge_mask=False,
                             write_stop=n0t + p + depth)
    zeros_tail = (0,) * (nd - 1)
    in_map, out_map = wrapped_sweep_index_maps(n0t, p, depth)
    return pl.pallas_call(
        kern,
        grid=(n0tp + depth,),
        in_specs=[pl.BlockSpec(
            block, lambda j: in_map(j) + zeros_tail)],
        out_specs=pl.BlockSpec(
            block, lambda j: out_map(j) + zeros_tail),
        out_shape=jax.ShapeDtypeStruct(t.shape, t.dtype),
        scratch_shapes=[pltpu.VMEM((depth,) + block, t.dtype),
                        pltpu.VMEM((depth, r) + block[1:], t.dtype)],
        compiler_params=_sweep_compiler_params(spec, block, depth, t.dtype),
        interpret=interpret,
        metadata=SWEEP,
    )(t)


def stencil_nd_sweep_periodic(spec: StencilSpec, t: jax.Array, k: int,
                              t0: int, *, interpret: bool
                              ) -> jax.Array:
    """One fully-periodic k-step sweep on the layout-RESIDENT
    (n0, *mid, nb, m, vl) array — the ``ttile=1`` slice of
    :func:`stencil_nd_sweep_ttile` (see there for the wrapped-grid
    construction)."""
    return stencil_nd_sweep_ttile(spec, t, k, 1, t0, interpret=interpret)


# ---------------------------------------------------------------------------
# MXU matrixization engine: the sweep body as ONE banded-operator matmul.
#
# A depth-d advance of the resident (nb, m, vl) layout is a fixed linear
# map, so the whole lane-shift/Assemble arithmetic of the kernels above
# collapses into one `lax.dot_general` against the precomputed banded
# operator A^d (core/matrixize.py; A^d built by repeated squaring at
# TRACE time — the jitted program contains exactly one dot_general per
# sweep chunk and zero operator-construction matmuls, jaxpr-pinned).
#
# These sweeps deliberately run at the XLA level rather than inside a
# pallas_call: (1) pallas kernels cannot close over array constants
# ("consts not supported in pallas_call"), so the operator would have to
# ride as an extra input anyway; (2) a kernel body that is ONE matmul
# gains nothing over XLA's native MXU lowering of dot_general — on TPU
# this IS the MXU engine, and on CPU it avoids the interpret-mode
# penalty so the conformance matrix runs at full speed.  The engine
# still rides the resident layout end to end: periodic wrap via block-
# axis rolls on single-device runs, and the DISTRIBUTED ghost codec
# unchanged — the halo variants consume the same ghost-extended shards
# `halo.exchange_{blocks,axis,minor}` already build for the pallas
# engines, computing interior blocks only (corruption never enters: the
# band is exactly depth·r wide, so zero-filled ghost lanes beyond the
# exchanged strip multiply zero coefficients).
#
# Accumulation-dtype rules: bf16 inputs contract a bf16 operator with
# preferred_element_type=float32 (MXU-native), f32 in f32, f64 in f64
# (see matrixize.accum_dtype) — f64-oracle-checked in the conformance
# matrix.
# ---------------------------------------------------------------------------

def stencil1d_sweep_mxu(spec: StencilSpec, t: jax.Array, depth: int
                        ) -> jax.Array:
    """Advance the fully-periodic resident (nb, m, vl) layout by ``depth``
    steps with ONE dot_general against the banded operator A^depth."""
    from repro.core import matrixize
    nb, m, vl = t.shape
    op = matrixize.operator(spec, vl, m, depth)
    return matrixize.apply_banded(op, t)


def stencil_nd_sweep_mxu(spec: StencilSpec, t: jax.Array, depth: int
                         ) -> jax.Array:
    """n-D analogue: t is (n0, *mid, nb, m, vl); the banded operator
    carries the leading-axis tap offsets as periodic rolls and the
    minor-axis coupling (incl. lane carries) in its block matrices."""
    from repro.core import matrixize
    m, vl = t.shape[-2], t.shape[-1]
    op = matrixize.operator(spec, vl, m, depth)
    return matrixize.apply_banded(op, t)


def stencil1d_sweep_mxu_halo(spec: StencilSpec, t: jax.Array, depth: int,
                             block_halo: int) -> jax.Array:
    """Depth-``depth`` advance of a ghost-EXTENDED resident shard
    (nb + 2·block_halo blocks, ghosts exchanged by the distributed
    codec); returns the nb interior blocks — no redundant ghost-zone
    compute, no crop needed by the caller."""
    from repro.core import matrixize
    nb, m, vl = t.shape
    op = matrixize.operator(spec, vl, m, depth)
    assert block_halo >= op.block_reach(), (block_halo, op.block_reach())
    return matrixize.apply_banded(op, t, block_halo=block_halo)


def stencil_nd_sweep_mxu_halo(spec: StencilSpec, t: jax.Array, depth: int,
                              lead_halo, block_halo: int) -> jax.Array:
    """n-D halo variant: ``lead_halo[a]`` ghost rows per side on leading
    axis ``a`` (0 → the axis is undecomposed and wraps periodically),
    ``block_halo`` ghost blocks per side on the minor block axis."""
    from repro.core import matrixize
    m, vl = t.shape[-2], t.shape[-1]
    op = matrixize.operator(spec, vl, m, depth)
    assert block_halo == 0 or block_halo >= op.block_reach()
    return matrixize.apply_banded(op, t, lead_halo=lead_halo,
                                  block_halo=block_halo)


# ---------------------------------------------------------------------------
# §3.5 — block transpose kernel (the layout transform itself).
# ---------------------------------------------------------------------------

def _kernel_transpose(x_ref, o_ref):
    o_ref[...] = jnp.swapaxes(x_ref[...], -1, -2)


def block_transpose(x: jax.Array, vl: int, m: int,
                    *, interpret: bool, blocks_per_step: int = 8
                    ) -> jax.Array:
    """(N,) → (nb, m, vl) transpose layout via an in-VMEM tile transpose.

    On TPU each (vl, m) → (m, vl) tile transpose lowers to the Mosaic
    sublane/lane transpose unit — the structural analogue of the paper's
    8-instruction in-register transpose; we never materialize a global DLT.
    """
    n = x.shape[-1]
    nb = n // (vl * m)
    assert n % (vl * m) == 0
    g = max(1, min(blocks_per_step, nb))
    while nb % g:
        g -= 1
    xb = x.reshape(nb, vl, m)
    return pl.pallas_call(
        _kernel_transpose,
        grid=(nb // g,),
        in_specs=[pl.BlockSpec((g, vl, m), lambda j: (j, 0, 0))],
        out_specs=pl.BlockSpec((g, m, vl), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, m, vl), x.dtype),
        interpret=interpret,
        metadata=LAYOUT,
    )(xb)


def block_untranspose(t: jax.Array, vl: int, m: int,
                      *, interpret: bool, blocks_per_step: int = 8
                      ) -> jax.Array:
    nb = t.shape[0]
    g = max(1, min(blocks_per_step, nb))
    while nb % g:
        g -= 1
    out = pl.pallas_call(
        _kernel_transpose,
        grid=(nb // g,),
        in_specs=[pl.BlockSpec((g, m, vl), lambda j: (j, 0, 0))],
        out_specs=pl.BlockSpec((g, vl, m), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, vl, m), t.dtype),
        interpret=interpret,
        metadata=LAYOUT,
    )(t)
    return out.reshape(nb * vl * m)


# ---------------------------------------------------------------------------
# Baseline one-step kernels (for the layout A/B comparison in benchmarks):
# natural layout with cross-lane rolls vs transpose layout.
# ---------------------------------------------------------------------------

def _kernel_naive_1d(x_ref, o_ref, *, spec: StencilSpec):
    x = x_ref[...]                                # (rows, vl) natural layout
    rows, vl = x.shape
    acc = None
    for off, c in spec.taps:
        o = off[-1]
        # natural layout: +1 spatial shift crosses lanes — the data
        # alignment conflict: a full cross-lane roll per tap.
        sl = jnp.roll(x.reshape(-1), -o).reshape(rows, vl)
        term = sl * jnp.asarray(c, x.dtype)
        acc = term if acc is None else acc + term
    o_ref[...] = acc


def stencil1d_naive_onestep(spec: StencilSpec, x: jax.Array, vl: int = DEFAULT_VL,
                            *, interpret: bool) -> jax.Array:
    """One periodic step, natural layout: per-tap 128-lane rolls (baseline)."""
    n = x.shape[-1]
    assert n % vl == 0
    xb = x.reshape(n // vl, vl)
    out = pl.pallas_call(
        functools.partial(_kernel_naive_1d, spec=spec),
        grid=(1,),
        in_specs=[pl.BlockSpec(xb.shape, lambda j: (0, 0))],
        out_specs=pl.BlockSpec(xb.shape, lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(xb.shape, x.dtype),
        interpret=interpret,
        metadata=SWEEP,
    )(xb)
    return out.reshape(n)


def _kernel_transpose_1d(t_ref, o_ref, *, spec: StencilSpec):
    t = t_ref[...]                                # (nb, m, vl)
    m = t.shape[-2]
    ext = extend_vs(t, spec.r)
    o_ref[...] = _tap_sum_nd(spec, ext, m)


def _tap_sum_nd(spec, ext, m):
    r = spec.r
    acc = None
    for off, c in spec.taps:
        sl = lax.slice_in_dim(ext, r + off[-1], r + off[-1] + m,
                              axis=ext.ndim - 2)
        term = sl * jnp.asarray(c, ext.dtype)
        acc = term if acc is None else acc + term
    return acc


def stencil1d_transpose_onestep(spec: StencilSpec, t: jax.Array,
                                *, interpret: bool) -> jax.Array:
    """One periodic step in the transpose layout: per vector set, 2r
    assembled rows (lane-carry) + pure second-minor slices."""
    nb, m, vl = t.shape
    return pl.pallas_call(
        functools.partial(_kernel_transpose_1d, spec=spec),
        grid=(1,),
        in_specs=[pl.BlockSpec(t.shape, lambda j: (0, 0, 0))],
        out_specs=pl.BlockSpec(t.shape, lambda j: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(t.shape, t.dtype),
        interpret=interpret,
        metadata=SWEEP,
    )(t)
