"""Recursive jaxpr introspection — the single walker behind every layout
invariant pin.

Before this module, three test files carried copy-pasted jaxpr walkers
(``_count_prims`` ×2, ``_transpose_census``/``_pallas_grids``/
``_ppermute_operand_shapes``/``_dot_general_count``) that descended one
``call_jaxpr`` level per `params` value: a jaxpr nested inside a dict
param or a deeper container (tuple-of-tuples of branches, grid-mapping
attributes) was silently skipped, so an invariant violated inside a
``scan``-in-``pjit``-nested body could hide from the pin.  :func:`walk`
is the shared, genuinely-recursive replacement: it descends **every**
sub-jaxpr reachable from an equation's params at any container depth —
``pjit``/``scan``/``while``/``cond``/``shard_map`` call jaxprs, and
(optionally) ``pallas_call`` kernel bodies — and yields each equation
as a :class:`Site` carrying its program-order ordinal, nesting depth,
loop membership, and a conservative ppermute-taint flag (does any input
transitively derive from a collective?  the overlap invariant keys on
an interior kernel being ring-independent).

The walker feeds two consumers:

* the compatibility helpers (:func:`count_prims`,
  :func:`transpose_census`, :func:`pallas_grids`,
  :func:`ppermute_operand_shapes`, :func:`dot_general_count`) that the
  test-suite pins route through — semantics pinned to the historical
  walkers so no pin moved;
* :func:`program_facts`, the structured :class:`ProgramFacts` extraction
  the invariant registry (:mod:`repro.analysis.invariants`) evaluates.
"""
from __future__ import annotations

import dataclasses
from collections import Counter

import numpy as np
from jax.extend import core as jcore

#: control-flow primitives whose bodies are "the sweep loop" for the
#: resident-layout census (matches the historical test walkers)
LOOP_PRIMS = ("while", "scan")

#: primitives that move whole arrays between kernels — the resident
#: engine's zero-copy contract forbids them outside kernel bodies
COPY_PRIMS = ("pad", "concatenate", "slice", "dynamic_slice",
              "dynamic_update_slice", "gather")

#: mesh axis the distributed ring rides: ``mesh_for_shards`` names the
#: mesh axis decomposing spatial axis i ``d{i}``, and the overlapped
#: halo exchange always rides the LEAD spatial axis (``decomp[0]``) —
#: the minor-axis lane-ghost codec uses the higher ``d{i}`` names.
RING_AXIS = "d0"


def ppermute_axis_names(eqn) -> tuple[str, ...]:
    names = eqn.params.get("axis_name")
    if names is None:
        return ()
    if isinstance(names, (tuple, list)):
        return tuple(str(n) for n in names)
    return (str(names),)


def _is_ring_ppermute(eqn) -> bool:
    return eqn.primitive.name == "ppermute" \
        and RING_AXIS in ppermute_axis_names(eqn)


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def _param_jaxprs(eqn):
    """Every jaxpr reachable from ``eqn.params``, at ANY container depth
    (direct values, tuples/lists of any nesting, dict values) — the
    full-recursion fix over the historical one-level walkers."""
    def from_value(v):
        if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
            yield _as_jaxpr(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from from_value(item)
        elif isinstance(v, dict):
            for item in v.values():
                yield from from_value(item)
    for v in eqn.params.values():
        yield from from_value(v)


@dataclasses.dataclass(frozen=True)
class Site:
    """One equation of the walked program, in depth-first program order."""
    eqn: object
    ordinal: int          # depth-first visitation index (program order)
    depth: int            # call-jaxpr nesting depth (0 = top level)
    in_loop: bool         # inside a while/scan body
    in_pallas: bool       # inside a pallas_call kernel body
    tainted: bool         # an input transitively derives from a ppermute

    @property
    def prim(self) -> str:
        return self.eqn.primitive.name


def walk(closed, *, enter_pallas: bool = False,
         taint_source=None) -> list[Site]:
    """Depth-first walk of ``closed`` (ClosedJaxpr or Jaxpr) descending
    every reachable sub-jaxpr; kernel bodies only when ``enter_pallas``
    (the census default skips them: in-VMEM ops are free of HBM traffic,
    and the historical pins measured what XLA moves *between* kernels).

    Taint is per-body dataflow from the outputs of every equation
    ``taint_source`` selects (default: any ``ppermute``; the overlap
    invariant narrows it to the ring-axis ppermutes, since the interior
    kernel legitimately consumes the minor-axis lane-ghost exchange):
    entering a call body maps the caller's tainted operands onto the
    body's invars by trailing position (call conventions put consts
    first, so the carried args align from the right); a body whose
    outvars are tainted taints the call's outvars.  Taint is NOT carried
    around loop back-edges — the overlap invariant asks whether the
    interior kernel depends on *this iteration's* ring, which is exactly
    the static body dataflow.
    """
    if taint_source is None:
        taint_source = lambda eqn: eqn.primitive.name == "ppermute"
    sites: list[Site] = []
    counter = [0]

    def visit(jaxpr, depth, in_loop, in_pallas, tainted):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_tainted = any(isinstance(v, jcore.Var) and v in tainted
                             for v in eqn.invars)
            sites.append(Site(eqn, counter[0], depth, in_loop, in_pallas,
                              in_tainted))
            counter[0] += 1
            sub_tainted_out = False
            if enter_pallas or prim != "pallas_call":
                deeper_loop = in_loop or prim in LOOP_PRIMS
                for sub in _param_jaxprs(eqn):
                    inner = set()
                    for ov, iv in zip(reversed(eqn.invars),
                                      reversed(sub.invars)):
                        if isinstance(ov, jcore.Var) and ov in tainted:
                            inner.add(iv)
                    sub_tainted_out |= visit(
                        sub, depth + 1, deeper_loop,
                        in_pallas or prim == "pallas_call", inner)
            if taint_source(eqn) or in_tainted or sub_tainted_out:
                tainted.update(v for v in eqn.outvars
                               if isinstance(v, jcore.Var))
        return any(isinstance(v, jcore.Var) and v in tainted
                   for v in jaxpr.outvars)

    visit(_as_jaxpr(closed), 0, False, False, set())
    return sites


# ---------------------------------------------------------------------------
# compatibility helpers — the shared replacements for the historical
# test-local walkers (pins unchanged)
# ---------------------------------------------------------------------------

def count_prims(closed, *, enter_pallas: bool = False) -> Counter:
    """Primitive census.  ``enter_pallas=False`` counts the
    ``pallas_call`` equation but not its kernel body (the resident-sweep
    census); ``True`` descends kernel bodies too (the mxu census)."""
    c: Counter = Counter()
    for s in walk(closed, enter_pallas=enter_pallas):
        c[s.prim] += 1
    return c


def transpose_census(closed) -> tuple[int, int]:
    """(transposes outside any loop body, transposes inside loop bodies),
    not descending into pallas kernel bodies."""
    top = inside = 0
    for s in walk(closed):
        if s.prim == "transpose":
            if s.in_loop:
                inside += 1
            else:
                top += 1
    return top, inside


def pallas_grids(closed) -> list[tuple[int, ...]]:
    """Grids of every pallas_call in the program."""
    return [tuple(s.eqn.params["grid_mapping"].grid)
            for s in walk(closed) if s.prim == "pallas_call"]


def ppermute_operand_shapes(closed) -> list[tuple[int, ...]]:
    """Operand shapes of every ppermute in the program."""
    return [tuple(s.eqn.invars[0].aval.shape)
            for s in walk(closed) if s.prim == "ppermute"]


def dot_general_count(closed) -> int:
    return count_prims(closed, enter_pallas=True)["dot_general"]


def max_call_depth(closed) -> int:
    """Deepest call-jaxpr nesting reached — the full-recursion pin."""
    return max((s.depth for s in walk(closed, enter_pallas=True)),
               default=0)


# ---------------------------------------------------------------------------
# structured facts for the invariant registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PallasCallFacts:
    name: str
    grid: tuple
    ordinal: int
    in_loop: bool
    tainted: bool                 # consumes ANY ppermute-derived data
    ring_tainted: bool            # consumes RING_AXIS ppermute data only
    num_outputs: int
    input_output_aliases: tuple


@dataclasses.dataclass(frozen=True)
class PpermuteFacts:
    shape: tuple
    dtype: str
    nbytes: int
    ordinal: int
    in_loop: bool
    axis_names: tuple             # mesh axis names, e.g. ("d0",)

    @property
    def is_ring(self) -> bool:
        return RING_AXIS in self.axis_names


@dataclasses.dataclass(frozen=True)
class DotGeneralFacts:
    operand_dtype: str
    accum_dtype: str              # preferred_element_type, else out dtype
    ordinal: int
    in_loop: bool


@dataclasses.dataclass(frozen=True)
class ProgramFacts:
    """Everything the invariant registry reads off one traced program."""
    prims: Counter                       # census outside kernel bodies
    transposes_top: int
    transposes_in_loop: int
    reshapes_top: int
    reshapes_in_loop: int
    copies: int                          # COPY_PRIMS between kernels
    pallas_calls: tuple
    ppermutes: tuple
    dot_generals: tuple
    donated: bool                        # any pjit donated_invars set
    max_depth: int

    @property
    def hbm_roundtrips(self) -> int:
        """Kernel launch sites + inter-kernel copy prims — each one is
        at least a full pass over HBM-resident data per execution."""
        return len(self.pallas_calls) + self.copies


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    if info is None:
        return "pallas_call"
    return str(info).split()[0] or "pallas_call"


def program_facts(closed) -> ProgramFacts:
    prims: Counter = Counter()
    t_top = t_loop = r_top = r_loop = copies = max_depth = 0
    pallas, pperm, dots = [], [], []
    donated = False
    # second walk with taint narrowed to the ring-axis ppermutes: the
    # overlap invariant must not count the minor-axis lane-ghost codec
    # (which the interior kernel legitimately consumes) as ring data
    ring_tainted = {s.ordinal: s.tainted for s in walk(
        closed, enter_pallas=False, taint_source=_is_ring_ppermute)}
    for s in walk(closed, enter_pallas=False):
        prims[s.prim] += 1
        max_depth = max(max_depth, s.depth)
        if s.prim == "transpose":
            t_loop += s.in_loop
            t_top += not s.in_loop
        elif s.prim == "reshape":
            r_loop += s.in_loop
            r_top += not s.in_loop
        if s.prim in COPY_PRIMS:
            copies += 1
        if s.prim == "pallas_call":
            gm = s.eqn.params["grid_mapping"]
            pallas.append(PallasCallFacts(
                name=_kernel_name(s.eqn), grid=tuple(gm.grid),
                ordinal=s.ordinal, in_loop=s.in_loop, tainted=s.tainted,
                ring_tainted=ring_tainted[s.ordinal],
                num_outputs=int(gm.num_outputs),
                input_output_aliases=tuple(
                    s.eqn.params.get("input_output_aliases", ()) or ())))
        elif s.prim == "ppermute":
            aval = s.eqn.invars[0].aval
            shape = tuple(aval.shape)
            pperm.append(PpermuteFacts(
                shape=shape, dtype=np.dtype(aval.dtype).name,
                nbytes=int(np.prod(shape)) * np.dtype(aval.dtype).itemsize,
                ordinal=s.ordinal, in_loop=s.in_loop,
                axis_names=ppermute_axis_names(s.eqn)))
        elif s.prim == "dot_general":
            pet = s.eqn.params.get("preferred_element_type")
            accum = pet if pet is not None else s.eqn.outvars[0].aval.dtype
            dots.append(DotGeneralFacts(
                operand_dtype=np.dtype(s.eqn.invars[0].aval.dtype).name,
                accum_dtype=np.dtype(accum).name,
                ordinal=s.ordinal, in_loop=s.in_loop))
        if any(s.eqn.params.get("donated_invars") or ()):
            donated = True
    return ProgramFacts(
        prims=prims, transposes_top=t_top, transposes_in_loop=t_loop,
        reshapes_top=r_top, reshapes_in_loop=r_loop, copies=copies,
        pallas_calls=tuple(pallas), ppermutes=tuple(pperm),
        dot_generals=tuple(dots), donated=donated, max_depth=max_depth)
