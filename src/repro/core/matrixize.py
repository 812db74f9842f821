"""Banded-operator matrixization of stencil sweeps — the ``mxu`` engine.

The transpose layout (§3.2, ``core/layouts.py``) folds the minor axis into
(nb, m, vl) blocks, and one Jacobi step is a *fixed linear map* over that
layout: every output element of block ``b`` is a coefficient-weighted sum
of elements of blocks ``b-1, b, b+1`` (for r ≤ vl·m).  That map is a small
banded matrix — so the whole sweep body can be ONE
``jax.lax.dot_general`` against a precomputed operator, engaging the TPU
MXU instead of VPU lane-shift arithmetic, and the paper's time
unroll-and-jam becomes a matrix *power*: the depth-d operator ``A^d``
(one matmul advances d steps) is built **at trace time by repeated
squaring** on the band representation (PAPERS.md: *Stencil
Matrixization*, 2310.16298; *Temporal Vectorization*, 2010.04868).

Representation
--------------
A band is a dict ``{offsets: (B, B) float64 matrix}`` with
``B = vl·m`` and ``offsets = (lead-axis shifts…, block shift)``:

    out[i0.., b][:] = Σ_off  band[off] @ x[i0+o0.., b+ob][:]

where ``[:]`` is the block tile flattened in LAYOUT order (row s, lane j
→ flat ``s·vl + j``; natural in-block index ``j·m + s``).  Leading-axis
taps of an n-D stencil are diagonal in the tile coordinate; only the
minor-axis taps couple tile positions (including the lane-carry
boundary columns that read the neighbor block's ghost lanes — the
paper's Assemble, baked into the ``ob = ±1`` matrices).  Band products
convolve offsets (``C[oa+ob] += A[oa] @ B[ob]``), so ``A^d`` by repeated
squaring costs O(log d) *numpy* band products at plan-construction time
— the jitted program contains ZERO operator-construction matmuls, only
the one application ``dot_general`` per sweep chunk (jaxpr-pinned in
tests/test_matrixize.py).

Application (``apply_banded``) gathers the offset neighborhood — periodic
``roll`` on undecomposed axes, ghost-halo *slices* on decomposed axes
(the distributed ghost codec in ``distributed/halo.py`` fills those
ghosts, unchanged) — concatenates it on the tile axis, and contracts with
the packed ``(n_off·B, B)`` table in ONE ``dot_general``.

Accumulation-dtype rules (tested in the f64-oracle conformance matrix):
bf16 inputs contract a bf16-cast operator with
``preferred_element_type=float32`` (the MXU's native accumulate) and
cast back; f32 contracts in f32; f64 (x64 conformance) in f64.  The
operator itself is always constructed in float64.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.stencils import StencilSpec

Offsets = tuple[int, ...]  # (leading-axis offsets…, block-axis offset)

# Legality budget for the packed f32 operator table (the band of a
# depth-d power of an n-D stencil has up to (2dr+1)^(ndim-1)·(2p+1)
# offsets of B² coefficients each — the gate bounds it BEFORE
# construction so an illegal candidate never allocates).
OPERATOR_BUDGET = int(os.environ.get("REPRO_MXU_OPERATOR_BUDGET", 2 << 20))


def layout_perm(vl: int, m: int) -> np.ndarray:
    """natural in-block index ``j·m + s`` → layout-flat index ``s·vl + j``."""
    i = np.arange(vl * m)
    return (i % m) * vl + (i // m)


def one_step_band(spec: StencilSpec, vl: int, m: int
                  ) -> dict[Offsets, np.ndarray]:
    """The single-step linear map of ``stencils.apply_once`` (periodic) on
    one (m, vl) tile, as a band of (B, B) float64 matrices."""
    B = vl * m
    perm = layout_perm(vl, m)
    band: dict[Offsets, np.ndarray] = {}
    for off, c in spec.taps:
        lead, om = tuple(off[:-1]), off[-1]
        for i in range(B):
            j_nat = i + om
            key = lead + (j_nat // B,)
            mat = band.setdefault(key, np.zeros((B, B), np.float64))
            mat[perm[i], perm[j_nat % B]] += c
    return band


def band_mul(a: dict[Offsets, np.ndarray],
             b: dict[Offsets, np.ndarray]) -> dict[Offsets, np.ndarray]:
    """Composition (apply ``b`` first, then ``a``): offsets convolve,
    coefficient matrices multiply."""
    out: dict[Offsets, np.ndarray] = {}
    for oa, ma in a.items():
        for ob, mb in b.items():
            key = tuple(x + y for x, y in zip(oa, ob))
            prod = ma @ mb
            if key in out:
                out[key] = out[key] + prod
            else:
                out[key] = prod
    return out


def band_power(band: dict[Offsets, np.ndarray], d: int
               ) -> dict[Offsets, np.ndarray]:
    """``band^d`` by repeated squaring — O(log d) band products, all at
    construction (numpy) time."""
    assert d >= 1, d
    result = None
    sq = band
    while d:
        if d & 1:
            result = sq if result is None else band_mul(result, sq)
        d >>= 1
        if d:
            sq = band_mul(sq, sq)
    return {k: v for k, v in result.items() if v.any()}


@dataclasses.dataclass(frozen=True)
class BandedOperator:
    """A packed depth-``depth`` advance operator for one (vl, m) layout.

    ``table[kidx·B + j, i] = A_off[i, j]`` for ``off = offsets[kidx]`` —
    pre-transposed so application is ``X_neighborhood @ table``."""
    ndim: int
    vl: int
    m: int
    depth: int
    offsets: tuple[Offsets, ...]
    table: np.ndarray            # (n_off·B, B) float64

    @property
    def B(self) -> int:
        return self.vl * self.m

    @property
    def n_off(self) -> int:
        return len(self.offsets)

    def block_reach(self) -> int:
        """Max |block-axis offset| — ghost blocks needed per side."""
        return max(abs(o[-1]) for o in self.offsets)

    def lead_reach(self, axis: int) -> int:
        """Max |offset| along leading axis ``axis`` — ghost rows needed."""
        return max(abs(o[axis]) for o in self.offsets)


@functools.lru_cache(maxsize=256)
def operator(spec: StencilSpec, vl: int, m: int,
             depth: int) -> BandedOperator:
    """The depth-``depth`` banded advance operator, built once per
    (spec, vl, m, depth) and cached — plans close over it; the jitted
    program embeds the packed table as a constant."""
    band = band_power(one_step_band(spec, vl, m), depth)
    offsets = tuple(sorted(band))
    table = np.concatenate([band[o].T for o in offsets], axis=0)
    return BandedOperator(spec.ndim, vl, m, depth, offsets,
                          np.ascontiguousarray(table))


def operator_bytes_bound(spec: StencilSpec, vl: int, m: int,
                         depth: int) -> int:
    """Upper bound on the packed f32 operator size, WITHOUT constructing:
    (2·depth·r+1)^(ndim-1) leading offsets × (2p+1) block offsets × B²
    coefficients (p = ghost blocks the band can reach)."""
    B = vl * m
    p = -(-depth * spec.r // B)
    n_off = (2 * depth * spec.r + 1) ** (spec.ndim - 1) * (2 * p + 1)
    return n_off * B * B * 4


def accum_dtype(dtype) -> jnp.dtype:
    """MXU accumulation rule: bf16/f32 accumulate in f32, f64 in f64."""
    return jnp.dtype(jnp.float64) if jnp.dtype(dtype) == jnp.float64 \
        else jnp.dtype(jnp.float32)


def contraction_precision(dtype) -> lax.Precision | None:
    """MXU pass rule: bf16 operands are exact in one pass; wider operands
    ask for ``HIGHEST``, since the TPU's default rounds them to bf16."""
    return None if jnp.dtype(dtype).itemsize <= 2 else lax.Precision.HIGHEST


def apply_banded(op: BandedOperator, t, lead_halo=None, block_halo: int = 0):
    """Advance the resident layout ``t`` by ``op.depth`` steps with ONE
    ``dot_general``.

    t: (lead axes…, nb, m, vl) — possibly ghost-extended.  Per axis the
    neighborhood gathers by periodic ``roll`` (halo 0: the axis wraps
    globally) or by ghost-halo slice (halo > 0: a decomposed axis whose
    ghosts the distributed codec filled; the output drops them, so only
    interior blocks are computed — the mxu engine does NO redundant
    ghost-zone compute).  ``lead_halo``: ghost rows per side per leading
    axis; ``block_halo``: ghost blocks per side on the block axis."""
    nlead = op.ndim - 1
    lead_halo = tuple(lead_halo or (0,) * nlead)
    assert len(lead_halo) == nlead, (lead_halo, op.ndim)
    B = op.B
    tb = t.reshape(t.shape[:-2] + (B,))     # (lead…, nb, B) layout-flat tiles
    nd = tb.ndim

    def gather(off: Offsets):
        s = tb
        idx = [slice(None)] * nd
        sliced = False
        for a, o in enumerate(off[:-1]):
            ax = nd - 2 - nlead + a
            if lead_halo[a]:
                n = tb.shape[ax] - 2 * lead_halo[a]
                idx[ax] = slice(lead_halo[a] + o, lead_halo[a] + o + n)
                sliced = True
            elif o:
                s = jnp.roll(s, -o, axis=ax)
        if block_halo:
            nbl = tb.shape[-2] - 2 * block_halo
            idx[-2] = slice(block_halo + off[-1], block_halo + off[-1] + nbl)
            sliced = True
        elif off[-1]:
            s = jnp.roll(s, -off[-1], axis=-2)
        return s[tuple(idx)] if sliced else s

    parts = [gather(off) for off in op.offsets]
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    table = jnp.asarray(op.table.astype(t.dtype))
    acc = lax.dot_general(
        x, table, (((x.ndim - 1,), (0,)), ((), ())),
        precision=contraction_precision(t.dtype),
        preferred_element_type=accum_dtype(t.dtype))
    out = acc.astype(t.dtype)
    return out.reshape(out.shape[:-1] + (op.m, op.vl))
