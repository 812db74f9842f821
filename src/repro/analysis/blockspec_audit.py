"""Pallas BlockSpec race-and-bounds checker.

A ``pallas_call``'s BlockSpec index maps are tiny pure jaxprs of the
grid indices, so they can be **concretely enumerated** over the full
grid at audit time — no kernel execution, no Mosaic compile.  For each
kernel this checker evaluates every input and output index map at every
grid point and flags:

* ``blockspec-oob-read`` — an input map that addresses a block outside
  the operand's footprint.  The wrapped-halo kernels
  (``kernels/stencil_kernels.wrapped_sweep_index_maps``) keep every
  read inside ``[0, nblocks)`` by construction (the ``mod`` wrap); a
  map that lost its wrap produces negative or past-the-end indices and
  would read garbage (or fault) on real silicon.
* ``blockspec-oob-write`` — an output map addressing a block outside
  the output's footprint.
* ``blockspec-coverage-gap`` — an output block no grid step ever
  writes: the launch returns uninitialized memory there.
* ``blockspec-write-overlap`` — output blocks written from multiple
  grid steps *while other blocks go unwritten*: the signature of an
  overlapping output index map clobbering coverage (e.g. everything
  landing on block 0).  Revisits with full coverage are NOT flagged —
  the wrapped-grid sweep kernels deliberately re-write the corrupted
  head blocks later in the same (sequential) grid, final writer wins.
* ``blockspec-donate-alias`` — for a kernel-level input/output alias,
  a grid step that reads an input block some *earlier* step already
  wrote through the aliased output: with the buffers donated in place,
  the read observes clobbered data.

Grids above :data:`MAX_GRID_POINTS` points (none of ours) and dynamic
grids are skipped rather than guessed at.
"""
from __future__ import annotations

import dataclasses
import itertools

from jax.extend import core as jcore

from repro.analysis import jaxpr_audit

MAX_GRID_POINTS = 16384


@dataclasses.dataclass(frozen=True)
class BlockSpecFinding:
    kind: str
    kernel: str
    message: str

    def __str__(self):
        return f"{self.kind} [{self.kernel}]: {self.message}"


def _eval_index_map(im, point) -> tuple[int, ...]:
    return tuple(int(v) for v in
                 jcore.jaxpr_as_fun(im)(*point))


def _block_size(blk) -> int:
    """Extent of one block dim: a plain int, a ``Blocked``-style dim
    carrying ``block_size``, or 1 for a squeezed/mapped dim."""
    if isinstance(blk, int):
        return blk
    size = getattr(blk, "block_size", None)
    return size if isinstance(size, int) else 1


def _nblocks(arr_shape, block_shape) -> tuple[int, ...]:
    return tuple(-(-int(dim) // max(_block_size(blk), 1))
                 for dim, blk in zip(arr_shape, block_shape))


def _enumerate(bm, grid, points):
    """{grid point: block index} for one BlockMapping, or None if the
    index map is not a pure function of the grid indices."""
    im = bm.index_map_jaxpr
    if len(im.jaxpr.invars) != len(grid):
        return None, None
    nb = _nblocks(tuple(bm.array_aval.shape),
                  tuple(bm.block_shape))
    return {pt: _eval_index_map(im, pt) for pt in points}, nb


def _oob(idx, nb) -> bool:
    return any(i < 0 or i >= n for i, n in zip(idx, nb))


def audit_pallas_call(eqn) -> list[BlockSpecFinding]:
    findings: list[BlockSpecFinding] = []
    gm = eqn.params["grid_mapping"]
    name = jaxpr_audit._kernel_name(eqn)
    grid = tuple(gm.grid)
    npoints = 1
    for g in grid:
        if not isinstance(g, int):
            return findings                      # dynamic grid
        npoints *= g
    if npoints == 0 or npoints > MAX_GRID_POINTS:
        return findings
    mappings = list(gm.block_mappings)
    n_out = int(gm.num_outputs)
    in_maps, out_maps = mappings[:len(mappings) - n_out], \
        mappings[len(mappings) - n_out:]
    points = list(itertools.product(*(range(g) for g in grid)))

    reads, writes = [], []
    for bm in in_maps:
        idxs, nb = _enumerate(bm, grid, points)
        reads.append((idxs, nb))
        if idxs is None:
            continue
        bad = sorted({ix for ix in idxs.values() if _oob(ix, nb)})
        if bad:
            findings.append(BlockSpecFinding(
                "blockspec-oob-read", name,
                f"input index map reads outside the {nb}-block footprint "
                f"at {bad[:4]}{'…' if len(bad) > 4 else ''}"))
    for bm in out_maps:
        idxs, nb = _enumerate(bm, grid, points)
        writes.append((idxs, nb))
        if idxs is None:
            continue
        oob = sorted({ix for ix in idxs.values() if _oob(ix, nb)})
        if oob:
            findings.append(BlockSpecFinding(
                "blockspec-oob-write", name,
                f"output index map writes outside the {nb}-block "
                f"footprint at {oob[:4]}{'…' if len(oob) > 4 else ''}"))
        written = [ix for ix in idxs.values() if not _oob(ix, nb)]
        covered = set(written)
        total = 1
        for n in nb:
            total *= n
        gaps = total - len(covered)
        if gaps:
            findings.append(BlockSpecFinding(
                "blockspec-coverage-gap", name,
                f"{gaps} of {total} output blocks are never written — "
                "the launch returns uninitialized memory there"))
            if len(written) > len(covered):
                findings.append(BlockSpecFinding(
                    "blockspec-write-overlap", name,
                    "output blocks written from multiple grid steps "
                    f"while {gaps} block(s) go unwritten — overlapping "
                    "output index map clobbers coverage"))

    # donate-alias hazard: aliased input read AFTER the aliased output
    # already wrote that block at an earlier (sequential) grid step
    aliases = dict(tuple(eqn.params.get("input_output_aliases", ())
                         or ()))
    for i_in, i_out in aliases.items():
        if i_in >= len(reads) or i_out >= len(writes):
            continue
        r_idxs, _ = reads[i_in]
        w_idxs, _ = writes[i_out]
        if r_idxs is None or w_idxs is None:
            continue
        seen: set = set()
        for pt in points:
            if r_idxs[pt] in seen:
                findings.append(BlockSpecFinding(
                    "blockspec-donate-alias", name,
                    f"aliased input {i_in} reads block {r_idxs[pt]} at "
                    f"grid step {pt} after the aliased output wrote it "
                    "at an earlier step — donated buffers observe "
                    "clobbered data"))
                break
            seen.add(w_idxs[pt])
    return findings


def audit_blockspecs(closed) -> list[BlockSpecFinding]:
    """Every BlockSpec finding of every pallas_call in the program."""
    out: list[BlockSpecFinding] = []
    for s in jaxpr_audit.walk(closed):
        if s.prim == "pallas_call":
            out.extend(audit_pallas_call(s.eqn))
    return out
