"""Unified cross-backend measured-search autotuner behind ``plan="auto"``.

The paper's performance hinges on picking the right vectorization
parameters — scheme, vector length ``vl``, transpose block ``m``,
unroll-and-jam factor ``k``, tessellation tile — per (stencil, shape,
dtype, backend).  This module turns that menu into a measured search
over **every execution backend at once**:

  1. :func:`candidate_plans` enumerates every *legal* ``StencilPlan`` for
     the problem.  ``backend="auto"`` (the default) pools the jnp
     schemes, the Pallas transpose-layout kernels AND — on a ≥2-device
     host — the distributed shard_map backend in one candidate list;
     each backend has explicit legality gates (:func:`pallas_plan_legal`:
     block-shape divisibility, halo-fits-block, pipeline-tile
     divisibility, sweep-engine validity; :func:`distributed_plan_legal`:
     shard divisibility, halo-fits-shard, ≥2 devices, local lane-block
     divisibility for the shard-resident Pallas engine — which, with the
     lane-carry ghost codec, accepts ANY mesh decomposition including
     minor-axis and 2-D+ meshes) instead of ad-hoc per-branch
     filtering.  Pallas candidates fan out along a
     ``sweep`` axis — ``resident`` (the layout-resident engine: one
     program per run, no per-sweep pad/transpose round-trips) vs
     ``roundtrip`` (legacy per-sweep wrap-pad/crop) — and the roofline
     ranks resident ahead because it amortizes the layout traffic over
     the run.  Distributed candidates fan out over (mesh decomposition ×
     k × local engine × sweep): the ``decomp`` plan axis carries the
     per-spatial-axis shard counts, so the mesh mapping and the
     time-block depth are chosen *jointly* by measurement.  Every
     resident-sweep candidate (single-device AND distributed)
     additionally fans out over the temporal-tile axis ``ttile`` ∈
     :data:`_TTILES`, gated by :func:`ttile_plan_legal` (halo slope
     fits the local extent, the kernel fits the chip's VMEM, the run is
     deep enough to amortize).  Off-TPU the
     auto pool caps pallas enumeration at
     :data:`INTERPRET_MAX_POINTS` grid points (interpret-mode
     measurement latency budget; explicit ``backend="pallas"`` /
     ``backend="distributed"`` bypasses it).
  2. the analytic roofline in :mod:`repro.roofline.stencil` ranks them
     (with a CPU interpret-mode penalty for Pallas kernels, see
     :data:`INTERPRET_PENALTY`), using per-device-kind constants fitted
     from earlier measured runs (:mod:`repro.roofline.calibrate`,
     persisted beside the plan cache — pruning sharpens as runs
     accumulate), and the top ``max_measure`` survive — the pool is
     *backend-stratified*: at least one candidate of every backend
     present in the pool is always measured, so no backend is ever
     silently skipped.
  3. every survivor is **statically audited** first
     (:mod:`repro.analysis`): its whole-run program is traced abstractly
     and the layout-invariant registry evaluated; a candidate with any
     violation is pruned with the violation named and is never timed
     (``REPRO_PLAN_AUDIT=0`` disables the gate).  Then the remaining
     survivors are timed with ``problem.run`` via
     :func:`repro.core.timing.bench` and the fastest wins; every timed
     sample also feeds the roofline calibrator.
  4. the winner is written to a persistent JSON plan cache keyed by
     problem signature + device signature (kind × count) + step count +
     code fingerprint, so every later run — including the serving path,
     which never measures — reuses it.

Per-``steps`` planning
----------------------

Plans are tuned for the *actual* step count of the run.  When ``steps``
is not divisible by the unroll factor ``k`` (or the tessellation height),
candidates carry a ``(k, remainder)`` axis instead of a hard-coded
fallback:

  * ``remainder="fused"``  — the historical policy: leftover
    ``steps % k`` steps run as single (k=1) steps on the same backend;
  * ``remainder="native"`` — the leftover runs as ONE ``k=steps%k``
    block on the same backend (one extra pipelined sweep / one shorter
    tessellation round) — fewer memory round-trips, slightly more
    instruction variety.

Both variants are enumerated, roofline-ranked (the memory term amortizes
differently, see ``estimate_plan_time(..., steps=...)``) and measured
with the real remainder handling — over a window congruent to ``steps``
mod every block size, so tuning cost never scales with the run length —
and the cached winner is optimal for that exact ``steps``.  Step counts
every block divides are :func:`normalize_steps`-collapsed onto the
generic (``steps=None``) key, which also serves as the fallback for any
per-``steps`` miss.

Self-invalidating plan key
--------------------------

:func:`plan_key` embeds :func:`code_fingerprint` — a content hash of the
stencil registry (taps/coefficients), the scheme registry
(``vectorize.SCHEMES``, including the *source* of each registered kernel
fn) and the kernel/runtime module sources (``core/`` + ``kernels/``).
Editing any of that code — or monkeypatching a registered scheme —
changes every key, so stale cached plans are never served; they simply
stop matching and the tuner re-measures.

Plan-cache file format (JSON, ``REPRO_PLAN_CACHE`` env var or
``~/.cache/repro/plan_cache.json``)::

    {"version": 2,
     "entries": {
       "2d5p|512x512|float32|auto|cpux8|s32|3f2a9c1d04be": {
         "plan": {"scheme": "transpose", "k": 2, "tiling": "none",
                  "tile": null, "height": null, "vl": 8, "m": 8,
                  "backend": "jnp", "t0": null, "remainder": "fused",
                  "sweep": "resident", "decomp": null, "ttile": 1},
         "seconds_per_step": 1.2e-4,
         "fingerprint": "3f2a9c1d04be",
         "n_candidates": 23, "n_measured": 8,
         "measurements": [{"plan": {...}, "seconds_per_step": ...}, ...],
         "failures": [{"plan": {...}, "error": "..."}, ...]
       }}}

``measurements`` is the tuning log: one row per measured candidate, in
measurement order.  ``failures`` lists the candidates that raised while
being measured (on the chip: a kernel Mosaic refused).  Corrupt or
version-mismatched files are ignored (the tuner re-measures and
overwrites).
"""
from __future__ import annotations

import dataclasses
import hashlib
import inspect
import logging
import math
import os
import threading
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import locked_json, stencils
from repro.core.api import StencilPlan
from repro.core.timing import bench
from repro.roofline import calibrate
from repro.roofline.stencil import estimate_plan_time, plan_terms

logger = logging.getLogger("repro.autotune")

CACHE_VERSION = 2          # v2: keys carry steps + code fingerprint
CACHE_ENV = "REPRO_PLAN_CACHE"

# search space knobs
_VLS = (4, 8, 16)
_KS = (1, 2, 4)
_TTILES = (2, 4)          # temporal-tile factors enumerated for resident
#                           sweep candidates (ttile=1 is the base plan)
_HEIGHTS = (2, 4)         # tessellation heights enumerated below
_MEASURE_STEPS = 4        # lcm-friendly with every k in _KS
# lcm of every block size (unroll k, tessellation height) a candidate can
# carry: step counts congruent mod this value produce identical candidate
# pools and remainder behavior.
_BLOCK_LCM = math.lcm(*_KS, *_HEIGHTS)
_MAX_M_PER_VL = 4         # cap on the pallas m axis per vector length
_MAX_T0 = 2               # cap on the pallas pipeline-tile axis

# Pallas kernels execute in interpret mode off-TPU — orders of magnitude
# slower than compiled jnp.  The roofline can't see that, so the ranking
# applies this factor; stratification still measures >=1 pallas candidate.
INTERPRET_PENALTY = 50.0
# ...and measuring an interpret-mode candidate on a large grid costs real
# minutes, so the *auto* pool only enumerates pallas up to this many grid
# points off-TPU (one-time tuning latency budget; an explicit
# backend="pallas" request bypasses the gate).  Env-overridable.
INTERPRET_MAX_POINTS = int(os.environ.get(
    "REPRO_PALLAS_INTERPRET_MAX_POINTS", 1 << 18))

def default_cache_path() -> str:
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "plan_cache.json")


# shared with roofline.calibrate so the plan-cache device component and
# the calibration-file device keys can never diverge per chip kind
device_kind = calibrate.device_kind


def device_signature() -> str:
    """Device component of the plan key: kind × visible device count.

    The count matters now that the pool holds distributed candidates — a
    plan tuned on an 8-device host (whose winner may carry a ``decomp``
    needing all 8) must not be served on a 1-device host of the same
    chip kind."""
    return f"{device_kind()}x{jax.device_count()}"


# ---------------------------------------------------------------------------
# code fingerprint — the self-invalidation hash
# ---------------------------------------------------------------------------

_fp_memo: dict[tuple, str] = {}


def _source_of(obj) -> str:
    try:
        return inspect.getsource(obj)
    except (OSError, TypeError):
        return repr(obj)


def code_fingerprint() -> str:
    """12-hex content hash of the scheme registry + kernel sources.

    Covers: every registered :class:`StencilSpec` (name/ndim/r/kind/taps),
    every entry of ``vectorize.SCHEMES`` (name + kernel-fn *source*, so a
    monkeypatched scheme changes the hash), and the module sources of the
    execution layers a plan can dispatch to (``core/vectorize``,
    ``core/unroll_jam``, ``core/tessellate``, ``core/layouts``,
    ``core/matrixize``, ``core/api``, ``kernels/stencil_kernels``,
    ``kernels/ops``, ``distributed/halo``, ``distributed/multistep``).

    Memoized per registry *identity* (object ids), so the common case is a
    dict lookup; replacing a registry entry recomputes.
    """
    from repro.core import (api, layouts, matrixize, tessellate, unroll_jam,
                            vectorize)
    from repro.distributed import halo as dhalo
    from repro.distributed import multistep as dmultistep
    from repro.kernels import ops as kops
    from repro.kernels import stencil_kernels

    # the memo key holds the registry objects themselves (not ids): live
    # references cannot be garbage-collected and readdressed, so a reused
    # address can never alias a stale hash.  Names are unique, so sorting
    # never compares the (unorderable) second elements.
    memo_key = (
        tuple(sorted(vectorize.SCHEMES.items())),
        tuple(sorted(stencils._REGISTRY.items())),
    )
    hit = _fp_memo.get(memo_key)
    if hit is not None:
        return hit
    if len(_fp_memo) > 64:          # bound hot-reload / monkeypatch churn
        _fp_memo.clear()
    h = hashlib.sha256()
    for name, spec in sorted(stencils._REGISTRY.items()):
        h.update(repr((name, spec.ndim, spec.r, spec.kind,
                       spec.taps)).encode())
    for name in sorted(vectorize.SCHEMES):
        h.update(name.encode())
        h.update(_source_of(vectorize.SCHEMES[name]).encode())
    for mod in (vectorize, unroll_jam, tessellate, layouts, matrixize, api,
                stencil_kernels, kops, dhalo, dmultistep):
        h.update(_source_of(mod).encode())
    fp = h.hexdigest()[:12]
    _fp_memo[memo_key] = fp
    return fp


def normalize_steps(steps: int | None) -> int | None:
    """Collapse step counts every candidate block divides to the generic
    (``steps=None``) plan: congruent-mod-``_BLOCK_LCM`` step counts have
    identical candidate pools and remainder behavior, so keying (and
    re-measuring) per exact value would only fragment the cache."""
    if steps is not None and steps % _BLOCK_LCM == 0:
        return None
    return steps


def plan_key(spec_name: str, shape: Sequence[int], dtype, backend: str,
             device: str | None = None, steps: int | None = None) -> str:
    """Cache key: signature | device signature | step count | fingerprint.

    The device component is kind × device count (``cpux8``) — distributed
    winners carry a mesh decomposition, so plans tuned at one device
    count never leak to another.  ``steps=None`` produces the generic
    (any-step-count) key ``s*``; the fingerprint suffix makes every key
    stale the moment the scheme registry or kernel code changes (see
    :func:`code_fingerprint`).
    """
    device = device_signature() if device is None else device
    return "|".join([spec_name, "x".join(str(n) for n in shape),
                     jnp.dtype(dtype).name, backend, device,
                     f"s{'*' if steps is None else steps}",
                     code_fingerprint()])


def plan_to_dict(plan: StencilPlan) -> dict:
    d = dataclasses.asdict(plan)
    d["tile"] = list(plan.tile) if plan.tile is not None else None
    d["decomp"] = list(plan.decomp) if plan.decomp is not None else None
    return d


def plan_from_dict(d: dict) -> StencilPlan:
    d = dict(d)
    if d.get("tile") is not None:
        d["tile"] = tuple(d["tile"])
    if d.get("decomp") is not None:
        d["decomp"] = tuple(d["decomp"])
    return StencilPlan(**d)


# ---------------------------------------------------------------------------
# persistent plan cache
# ---------------------------------------------------------------------------

class PlanCache:
    """On-disk JSON plan cache; load-once, explicit save, atomic write.

    Thread-safe within the process: ``get_cache`` hands the same
    instance to ``warm_async``'s background tuner and request threads,
    so every access to the entry/dirty state goes through ``_tlock``
    (the cross-PROCESS discipline is the file lock in
    :mod:`repro.core.locked_json`).  A ``put()`` racing a ``save()``
    is never lost: only keys whose written record is still current are
    marked clean."""

    def __init__(self, path: str | None = None):
        self.path = path or default_cache_path()
        self._tlock = threading.Lock()
        self._entries: dict[str, dict] = {}
        self._mtime: int | None = None
        self._dirty: set[str] = set()      # put() since last load/save
        self._load()

    def _load(self):
        self._entries = {}
        self._mtime = None
        try:
            self._mtime = os.stat(self.path).st_mtime_ns
        except OSError:
            return
        raw = locked_json.read_json(self.path)
        if raw is not None and raw.get("version") == CACHE_VERSION:
            self._entries = dict(raw.get("entries", {}))

    def refresh(self):
        """Re-read the file if another process wrote it since our last
        read (a long-lived server picks up offline tuning runs).  Only
        *unsaved local* entries shadow the disk; everything loaded earlier
        is superseded by the newer file contents."""
        try:
            mtime = os.stat(self.path).st_mtime_ns
        except OSError:
            return
        with self._tlock:
            if mtime == self._mtime:
                return
            dirty = {k: self._entries[k] for k in self._dirty
                     if k in self._entries}
            self._load()
            self._entries.update(dirty)

    def get(self, key: str) -> dict | None:
        with self._tlock:
            return self._entries.get(key)

    def put(self, key: str, record: dict):
        with self._tlock:
            self._entries[key] = record
            self._dirty.add(key)

    def save(self):
        # read-merge-write under an exclusive file lock
        # (core/locked_json.py): concurrent tuners (serving host + bench,
        # say) sharing the default path must not erase each other's
        # entries.  Our unsaved entries win on key collision; the file
        # wins for everything else.
        written: dict[str, dict] = {}     # what THIS save persisted
        payload_entries: dict[str, dict] = {}

        def merge(raw: dict | None) -> dict:
            merged: dict[str, dict] = {}
            if raw is not None and raw.get("version") == CACHE_VERSION:
                merged = dict(raw.get("entries", {}))
            with self._tlock:
                written.update({k: self._entries[k] for k in self._dirty
                                if k in self._entries})
            merged.update(written)
            # prune entries tuned against retired code: their keys can
            # never match again (plan_key embeds the fingerprint), so
            # keeping them only grows the file without bound across code
            # edits.  Records without a fingerprint field are kept
            # (hand-written / test entries).
            fp = code_fingerprint()
            merged = {k: v for k, v in merged.items()
                      if v.get("fingerprint") in (None, fp)}
            payload_entries.update(merged)
            return {"version": CACHE_VERSION, "entries": merged}

        def snapshot():       # file lock still held: no cross-proc races
            with self._tlock:
                # adopt the persisted view, but a put() that raced this
                # save stays in memory AND stays dirty — only keys whose
                # written record is still current go clean
                fresh = {k: self._entries[k] for k in self._dirty
                         if k in self._entries}
                self._entries = dict(payload_entries)
                self._entries.update(fresh)
                self._dirty = {k for k in self._dirty
                               if self._entries.get(k)
                               is not written.get(k)}
                try:
                    self._mtime = os.stat(self.path).st_mtime_ns
                except OSError:
                    pass

        locked_json.locked_update(self.path, merge, on_written=snapshot)

    def __len__(self):
        return len(self._entries)


_caches: dict[str, PlanCache] = {}


def get_cache(path: str | None = None) -> PlanCache:
    """Process-wide cache instance per path (avoids re-reading the file on
    every ``plan="auto"`` call)."""
    path = path or default_cache_path()
    if path not in _caches:
        _caches[path] = PlanCache(path)
    return _caches[path]


# ---------------------------------------------------------------------------
# candidate enumeration + backend legality gates
# ---------------------------------------------------------------------------

def _layout_pairs(n: int, r: int):
    """Legal (vl, m) for jnp layout schemes on a unit-stride extent n:
    blocks of vl·m must tile n and the halo must fit inside one vector
    set."""
    out = []
    for vl in _VLS:
        for m in dict.fromkeys((vl, max(vl // 2, 1), 2 * vl)):
            if m < r:
                continue
            if n % (vl * m):
                continue
            out.append((vl, m))
    return out


def _schedule_max_depth(k: int, steps: int | None, remainder: str,
                        ttile: int = 1) -> int:
    """Deepest single launch of the run's sweep schedule — the depth the
    halo/slope legality gates must accommodate.  Schedule-aware: a
    ``steps < k`` run never executes the main k-block, so only the
    remainder's depth counts (the fix for ``remainder="native"`` plans
    whose k exceeds what the shard/grid supports but whose actual
    remainder block fits)."""
    from repro.core.api import sweep_schedule
    chunks, _ = sweep_schedule(k, steps, remainder, ttile)
    return max((d for d, _ in chunks), default=1)


def pallas_plan_legal(spec: stencils.StencilSpec, shape: Sequence[int],
                      vl: int, m: int, t0: int | None = None,
                      sweep: str = "resident", *, ttile: int = 1,
                      k: int | None = None, steps: int | None = None,
                      remainder: str = "fused") -> bool:
    """Backend legality gate for the Pallas transpose-layout kernels.

    * block-shape divisibility: ``shape[-1] % (vl*m) == 0`` — the
      (nb, m, vl) transposed array must tile the unit-stride extent
      exactly (this holds for *any* vl·m, power-of-two or not; the gate
      is what rejects non-dividing combinations);
    * halo-fits-block: ``r <= m`` and ``r <= vl`` (the kernels assemble
      at most r boundary rows per vector set, and carry r lanes);
    * lanes (n-D, compiled by Mosaic): ``vl`` a multiple of 128
      (:func:`_lanes_compile`);
    * pipeline tile (n-D only): ``t0`` must divide ``shape[0]`` and hold
      the halo (``t0 >= r``);
    * sweep engine: ``resident`` (layout-resident wrapped-grid sweeps) or
      ``roundtrip`` (per-sweep wrap-pad/crop).  The resident engine wraps
      its halo reads through the grid index maps, which is legal for any
      block count — it adds NO constraint beyond the shared gates above,
      so the two engines are interchangeable wherever pallas is legal;
    * temporal tile: ``ttile > 1`` requires the resident engine (the
      roundtrip path re-lays-out every sweep — there is nothing to
      temporally tile) and is further gated by :func:`ttile_plan_legal`
      (slope fits the extent, kernel fits VMEM);
    * schedule depth (only checked when ``k``/``steps`` are given): the
      deepest launch of the (k, steps, remainder, ttile) schedule —
      including a ``remainder="native"`` block of ``steps % k`` steps —
      must keep its halo slope ``depth·r`` within the pipelined extent,
      and its kernel must fit the chip's VMEM (:func:`_vmem_fits`).
      This is what rejects native-remainder plans whose leftover block
      is too deep for the grid, and tiles Mosaic would refuse, instead
      of letting them fail at run time.
    """
    if sweep not in ("resident", "roundtrip"):
        return False
    if ttile > 1 and sweep != "resident":
        return False
    n = shape[-1]
    r = spec.r
    if n % (vl * m) or m < r or vl < r or not _lanes_compile(spec, vl):
        return False
    if spec.ndim > 1:
        if t0 is None or t0 < r or shape[0] % t0:
            return False
    if k is not None:
        kmax = _schedule_max_depth(k, steps, remainder, ttile)
        n_pipe = shape[0] if spec.ndim > 1 else n
        if kmax * r > n_pipe:
            return False
        if not _vmem_fits(spec, shape, kmax, vl, m, t0):
            return False
    return True


def _lanes_compile(spec: stencils.StencilSpec, vl: int) -> bool:
    """Mosaic merges the (nb, vl) lane axes of an n-D tile into one lane
    row (``vectorize.extend_vs``), which it lowers for whole 128-lane
    vregs only; the interpreter takes any width."""
    from repro.kernels import ops, stencil_kernels as sk
    return spec.ndim == 1 or vl % sk.DEFAULT_VL == 0 \
        or ops._auto_interpret(None)


def _vmem_fits(spec: stencils.StencilSpec, local: Sequence[int],
               depth: int, vl: int, m: int, t0: int | None,
               itemsize: int = 4) -> bool:
    """Does a depth-``depth`` sweep kernel over the (local) extents fit
    the VMEM a kernel may claim (``stencil_kernels.sweep_vmem_bytes``)?
    The f32 default bounds bf16 too: its tiles pad to no more bytes."""
    from repro.kernels import stencil_kernels as sk
    if spec.ndim == 1:
        block = (1, m, vl)
    else:
        block = (t0 or 1,) + tuple(local[1:-1]) \
            + (local[-1] // (vl * m), m, vl)
    return sk.sweep_vmem_bytes(spec, block, depth, itemsize) \
        <= sk.VMEM_LIMIT_BYTES


def _pipeline_tiles(spec: stencils.StencilSpec, n0: int,
                    row: int) -> list[int]:
    """Pipeline tiles ``t0`` the n-D pools enumerate (largest first):
    powers of two up to 8 that divide ``n0`` and hold the halo, keeping
    the tile within ``ND_TILE_ELEMS`` (``row`` elements per t0-row) —
    or, when every such tile is larger, the default tiler's pick."""
    from repro.kernels import ops, stencil_kernels as sk
    t0s = [t for t in (8, 4, 2) if t <= n0 and n0 % t == 0 and t >= spec.r]
    small = [t for t in t0s if t * row <= sk.ND_TILE_ELEMS]
    if small:
        return small
    t0 = ops.pipeline_tile(n0, row, spec.r)
    return [t0] if t0 else []


def _kernel_lanes() -> tuple[int, ...]:
    """Lane widths of the Pallas pools: the chip's native 128 lanes where
    Mosaic compiles the kernels (:func:`pallas_plan_legal`); the narrow
    widths interpret-mode tuning can afford elsewhere."""
    from repro.kernels import ops, stencil_kernels as sk
    return _VLS if ops._auto_interpret(None) else (sk.DEFAULT_VL,)


def _pallas_pairs(n: int, r: int,
                  lanes: Sequence[int] = _VLS) -> list[tuple[int, int]]:
    """(vl, m) pairs for the Pallas backend: m ranges over divisors of
    n/vl (so non-power-of-two vl·m blocks are reachable when the extent
    calls for them), capped at ``_MAX_M_PER_VL`` per vl."""
    from repro.kernels import stencil_kernels as sk
    pairs = []
    for vl in lanes:
        if vl < r or n % vl:
            continue
        q = n // vl
        divisors = [m for m in range(max(r, 2), min(2 * vl, q) + 1)
                    if q % m == 0]
        # prefer the square-ish tiles the paper favors, then fill with the
        # remaining (possibly non-power-of-two) divisors
        keep = [m for m in dict.fromkeys((vl, vl // 2, 2 * vl, sk.DEFAULT_M))
                if m in divisors]
        for m in divisors:
            if len(keep) >= _MAX_M_PER_VL:
                break
            if m not in keep:
                keep.append(m)
        pairs += [(vl, m) for m in sorted(keep)]
    return pairs


def _with_remainder(plan: StencilPlan, steps: int | None, block: int,
                    native_ok: bool = True) -> list[StencilPlan]:
    """Per-``steps`` axis: when ``steps % block`` leaves a remainder, emit
    one candidate per remainder policy; otherwise the policy is inert and
    only the canonical (``fused``) variant is enumerated."""
    if steps is None or block <= 1 or steps % block == 0:
        return [plan]
    out = [dataclasses.replace(plan, remainder="fused")]
    if native_ok:
        out.append(dataclasses.replace(plan, remainder="native"))
    return out


def distributed_plan_legal(spec: stencils.StencilSpec,
                           shape: Sequence[int], decomp: Sequence[int],
                           k: int, engine: str = "jnp",
                           sweep: str = "resident", vl: int = 8,
                           m: int = 8, t0: int | None = None,
                           n_devices: int | None = None, *,
                           ttile: int = 1, steps: int | None = None,
                           remainder: str = "fused",
                           overlap: bool = False) -> bool:
    """Backend legality gate for distributed (shard_map halo) plans.

    * device availability: ``prod(decomp) == n_devices >= 2`` — the
      decomposition uses every visible device (partial meshes fragment
      the measurement pool without a matching serving story);
    * shard divisibility: every decomposed extent splits evenly;
    * halo-fits-shard: the ghost ring of the DEEPEST launch in the run's
      sweep schedule is sliced from the *neighbor's* local block, so
      ``depth·r <= local extent`` along every decomposed axis.  The
      depth is schedule-aware (see :func:`_schedule_max_depth`): with
      ``steps`` given, a ``remainder="native"`` leftover block of
      ``steps % k`` steps — or a k-block that ``steps < k`` never
      executes — is gated on what actually runs, and ``ttile > 1``
      widens the main blocks to ``ttile·k``;
    * ``engine="pallas"`` additionally requires the LOCAL minor extent
      to tile into (vl, m) lane blocks with the halo inside one block
      row (``m >= r``, ``vl >= r``) and — n-D — a pipeline tile ``t0``
      dividing the local leading extent.  ANY mesh decomposition is
      legal beyond that: the pipelined axis exchanges whole t0-row
      tiles, mid axes raw rows, and the minor axis runs the lane-carry
      ghost codec (``halo.exchange_minor``) — the per-axis halo-fits
      checks above already guarantee every whole-unit rounding fits the
      shard (the exchanged width rounds up within a divisible extent).
      The local sweep kernel must fit the chip's VMEM (:func:`_vmem_fits`).
      The ``sweep`` axis (resident | roundtrip) is validated here and
      interchangeable wherever the engine is legal (both exchange the
      same valid ghost cells).
    * ``overlap=True`` (interior/boundary halo overlap) requires the
      pallas RESIDENT engine, a decomposed pipelined axis (n-D), and a
      local shard deep enough to host the boundary sub-sweeps — the
      feasibility bound is :func:`repro.distributed.multistep._overlap_bounds`
      evaluated at the schedule's deepest chunk.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    decomp = tuple(int(s) for s in decomp)
    if len(decomp) != spec.ndim or any(s < 1 for s in decomp):
        return False
    ndev = int(np.prod(decomp))
    if ndev < 2 or ndev != n_devices:
        return False
    if any(n % s for n, s in zip(shape, decomp)):
        return False
    r = spec.r
    local = [n // s for n, s in zip(shape, decomp)]
    kmax = _schedule_max_depth(k, steps, remainder, ttile)
    if any(s > 1 and kmax * r > nl for nl, s in zip(local, decomp)):
        return False
    if ttile > 1 and sweep != "resident":
        return False
    if overlap and (engine != "pallas" or sweep != "resident"):
        return False
    if engine == "jnp":
        return True
    if engine != "pallas" or sweep not in ("resident", "roundtrip"):
        return False
    n_minor = local[-1]
    if vl < r or m < r or n_minor % (vl * m) or not _lanes_compile(spec, vl):
        return False
    if spec.ndim > 1 and (t0 is None or t0 < r or local[0] % t0):
        return False
    if not _vmem_fits(spec, local, kmax, vl, m, t0):
        return False
    if overlap:
        # interior/boundary overlap rides the axis-0 ring (n-D) or the
        # minor lane-carry ring (1-D) of the RESIDENT engine only, and
        # its boundary sub-sweeps span two whole-tile ghost extents of
        # own data (multistep._overlap_bounds)
        if spec.ndim > 1 and decomp[0] < 2:
            return False
        from repro.distributed.multistep import _overlap_bounds
        need, have = _overlap_bounds(spec, local, kmax, vl * m,
                                     t0 if t0 else 1)
        if need > have:
            return False
    return True


def mxu_plan_legal(spec: stencils.StencilSpec, shape: Sequence[int],
                   vl: int, m: int, dtype=jnp.float32, *,
                   decomp: Sequence[int] | None = None,
                   k: int | None = None, steps: int | None = None,
                   remainder: str = "fused", ttile: int = 1,
                   n_devices: int | None = None) -> bool:
    """Backend legality gate for the mxu (banded-operator matrixization)
    engine (``core/matrixize.py``).

    * dtype/accumulation rules: f32 (f32 accumulate), bf16 (f32-accumulate
      ``dot_general``), f64 (x64 conformance) — other dtypes have no
      defined accumulation contract and fail closed;
    * lane divisibility: the (local) minor extent must tile into
      (vl, m) blocks exactly — same fold the transpose layout needs;
    * band-fits-tile: the DEEPEST launch of the sweep schedule must keep
      its band width ``depth·r`` within one operator tile ``vl·m``, so
      the banded operator reaches at most the ±1 neighbor block (the
      ghost block the distributed codec exchanges — deeper bands would
      need multi-block ghost rings and quadratically fatter operators);
    * operator budget: the construction-free band bound
      (:func:`repro.core.matrixize.operator_bytes_bound`) must fit
      :data:`repro.core.matrixize.OPERATOR_BUDGET` — a depth-d power of
      an n-D stencil has O((2dr+1)^(ndim-1)) offset matrices, and an
      over-budget operator would blow VMEM/cache before it ever won;
    * ``decomp`` (distributed mxu): shard divisibility on every axis,
      the decomposition using every visible device, and the exact
      ``depth·r`` ghost ring fitting every decomposed local extent —
      same mesh rules as :func:`distributed_plan_legal`, applied to the
      LOCAL extents the shard-resident operator actually sees.
    """
    from repro.core import matrixize
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float64)):
        return False
    shape = tuple(shape)
    r = spec.r
    local = list(shape)
    if decomp is not None:
        if n_devices is None:
            n_devices = jax.device_count()
        decomp = tuple(int(s) for s in decomp)
        if len(decomp) != spec.ndim or any(s < 1 for s in decomp):
            return False
        ndev = int(np.prod(decomp))
        if ndev < 2 or ndev != n_devices:
            return False
        if any(n % s for n, s in zip(shape, decomp)):
            return False
        local = [n // s for n, s in zip(shape, decomp)]
    if vl < 1 or m < 1 or local[-1] % (vl * m):
        return False
    depth = _schedule_max_depth(k if k is not None else 1, steps,
                                remainder, ttile)
    if depth * r > vl * m:
        return False
    if decomp is not None and any(
            s > 1 and depth * r > nl for nl, s in zip(local, decomp)):
        return False
    return matrixize.operator_bytes_bound(spec, vl, m, depth) \
        <= matrixize.OPERATOR_BUDGET


def _mxu_candidates(spec: stencils.StencilSpec, shape: tuple[int, ...],
                    dtype, steps: int | None,
                    n_devices: int | None = None) -> list[StencilPlan]:
    """The mxu axis of the unified pool: (vl, m) operator tiles ×
    k × remainder × ttile, single-device AND over every legal mesh
    decomposition (the engine rides the distributed ghost codec with
    exact depth·r rings).  No interpret budget gate — the engine is
    jnp-level and runs native on every backend."""
    if n_devices is None:
        n_devices = jax.device_count()
    shape = tuple(shape)
    cands: list[StencilPlan] = []
    decomps: list[tuple[int, ...] | None] = [None]
    decomps += _decomps_for(spec.ndim, n_devices)
    for decomp in decomps:
        n_minor = shape[-1] // (decomp[-1] if decomp else 1)
        for vl, m in _pallas_pairs(n_minor, spec.r)[:2]:
            for k in _KS:
                base = StencilPlan(scheme="transpose", k=k, vl=vl, m=m,
                                   backend="mxu", decomp=decomp)
                variants = [
                    p for p in _with_remainder(base, steps, k)
                    if mxu_plan_legal(
                        spec, shape, vl, m, dtype, decomp=decomp, k=k,
                        steps=steps, remainder=p.remainder,
                        n_devices=n_devices)]
                cands += _ttile_fanout(spec, shape, variants, steps,
                                       n_devices=n_devices)
    return cands


def ttile_plan_legal(spec: stencils.StencilSpec, shape: Sequence[int],
                     plan: StencilPlan, steps: int | None = None,
                     itemsize: int = 4,
                     n_devices: int | None = None) -> bool:
    """Legality gate for the temporal-tile axis of a resident-sweep plan.

    ``ttile = 1`` is always legal (it IS the base resident plan).  For
    ``ttile > 1``:

    * engine: only the resident sweep engines time-tile — ``pallas`` with
      ``sweep="resident"`` or the ``distributed`` backend (whose local
      sweeps are resident by construction);
    * slope-fits-extent: a depth-``d = ttile·k`` trapezoid launch drags a
      halo slope of ``d·r`` points behind the sweep front; the pipelined
      extent of the LOCAL block (``local[0]`` for n-D, the full extent
      for 1-D) must hold it, or the wrapped grid re-reads blocks still
      being written (on the distributed backend this is the same bound
      as the ghost ring: ``d·r <= nl`` on every decomposed axis);
    * steps-amortizable: with ``steps`` given, at least one full
      ``ttile·k`` block must execute (``steps // k >= ttile``) — deeper
      tiles than the run are wasted redundant compute;
    * VMEM: the depth-``d`` kernel must fit the VMEM a kernel may
      claim (:func:`_vmem_fits`) — Mosaic refuses deep tiles on fat
      blocks at compile time.
    """
    tt = plan.ttile
    if tt < 1:
        return False
    if tt == 1:
        return True
    if plan.backend == "mxu":
        # the engine is resident by construction; a deeper tile only
        # fattens the banded operator, so the whole gate is the depth-
        # aware mxu legality check (band fits the (vl, m) tile, operator
        # fits the budget, ghost ring fits every decomposed extent) plus
        # steps-amortizability.
        if steps is not None and steps // max(plan.k, 1) < tt:
            return False
        vl = plan.vl if plan.m is not None else 8
        m = plan.m if plan.m is not None else 8
        return mxu_plan_legal(
            spec, shape, vl, m, decomp=plan.decomp, k=plan.k,
            steps=steps, remainder=plan.remainder, ttile=tt,
            n_devices=n_devices)
    if plan.backend == "pallas":
        if plan.sweep != "resident":
            return False
    elif plan.backend == "distributed":
        # the jnp engine's halo-extended sweeps are resident by
        # construction; the pallas engine must not be the per-exchange
        # roundtrip rendering
        if plan.scheme == "transpose" and plan.sweep != "resident":
            return False
    else:
        return False
    if steps is not None and steps // max(plan.k, 1) < tt:
        return False
    depth = tt * max(plan.k, 1)
    r = spec.r
    shape = tuple(shape)
    if plan.backend == "distributed":
        if plan.decomp is None:
            return False
        local = tuple(n // s for n, s in zip(shape, plan.decomp))
        if any(s > 1 and depth * r > nl
               for nl, s in zip(local, plan.decomp)):
            return False
    else:
        local = shape
    n_pipe = local[0] if spec.ndim > 1 else local[-1]
    if depth * r > n_pipe:
        return False
    uses_pallas = plan.backend == "pallas" or plan.scheme == "transpose"
    if uses_pallas:
        vl = plan.vl if plan.m is not None else 8
        m = plan.m if plan.m is not None else 8
        if not _vmem_fits(spec, local, depth, vl, m, plan.t0, itemsize):
            return False
    return True


def _ttile_fanout(spec: stencils.StencilSpec, shape: Sequence[int],
                  plans: list[StencilPlan], steps: int | None,
                  n_devices: int | None = None) -> list[StencilPlan]:
    """Fan resident-sweep candidates out along the temporal-tile axis:
    each legal base plan also enumerates ``ttile`` ∈ ``_TTILES`` variants
    that pass :func:`ttile_plan_legal`.  Base (ttile=1) plans always
    stay in the pool — the ttile variants trade redundant compute for
    HBM/ghost round-trips, and measurement decides."""
    out = list(plans)
    for plan in plans:
        for tt in _TTILES:
            cand = dataclasses.replace(plan, ttile=tt)
            if ttile_plan_legal(spec, shape, cand, steps,
                                n_devices=n_devices):
                out.append(cand)
    return out


def _decomps_for(ndim: int, n_devices: int) -> list[tuple[int, ...]]:
    """Candidate mesh decompositions: every ordered factorization of the
    device count over ALL spatial axes — axis-0, mid-axis, minor-axis
    and 2-D+ meshes alike (the lane-carry ghost codec makes every axis
    exchangeable in layout, so none is excluded a priori)."""
    if n_devices < 2:
        return []
    out: list[tuple[int, ...]] = []

    def rec(prefix: tuple[int, ...], rem: int):
        if len(prefix) == ndim - 1:
            out.append(prefix + (rem,))
            return
        for a in range(1, rem + 1):
            if rem % a == 0:
                rec(prefix + (a,), rem // a)

    rec((), n_devices)
    return out


def _distributed_candidates(spec: stencils.StencilSpec,
                            shape: tuple[int, ...], steps: int | None,
                            n_devices: int | None = None,
                            budget_gate: bool = False) -> list[StencilPlan]:
    """The (mesh decomposition × k × engine × sweep) distributed axis of
    the unified pool.  Local engines: "jnp" and the shard-resident /
    roundtrip Pallas pair — both over ANY mesh decomposition (minor-axis
    and 2-D+ meshes included; the lane-carry ghost codec exchanges the
    folded axis in layout)."""
    if n_devices is None:
        n_devices = jax.device_count()
    if n_devices < 2:
        return []
    shape = tuple(shape)
    pallas_ok = not (budget_gate and jax.default_backend() != "tpu"
                     and int(np.prod(shape)) > INTERPRET_MAX_POINTS)
    cands: list[StencilPlan] = []
    for decomp in _decomps_for(spec.ndim, n_devices):
        for k in _KS:
            base = StencilPlan(scheme="fused", k=k, backend="distributed",
                               decomp=decomp)
            jnp_variants = [
                p for p in _with_remainder(base, steps, k)
                if distributed_plan_legal(
                    spec, shape, decomp, k, "jnp", n_devices=n_devices,
                    steps=steps, remainder=p.remainder)]
            cands += _ttile_fanout(spec, shape, jnp_variants, steps)
            if not pallas_ok:
                continue
            # pallas engines: tiles are picked from the LOCAL extents —
            # the minor axis may itself be decomposed (lane-carry codec)
            n_minor = shape[-1] // decomp[-1]
            if spec.ndim == 1:
                t0s: list[int | None] = [None]
            else:
                local = [n // s for n, s in zip(shape, decomp)]
                t0s = _pipeline_tiles(spec, local[0],
                                      int(np.prod(local[1:])))[:1]
            for vl, m in _pallas_pairs(n_minor, spec.r,
                                       _kernel_lanes())[:2]:
                for t0 in t0s:
                    for swp in ("resident", "roundtrip"):
                        base = StencilPlan(
                            scheme="transpose", k=k, vl=vl, m=m, t0=t0,
                            backend="distributed", decomp=decomp,
                            sweep=swp)
                        variants = [
                            p for p in _with_remainder(base, steps, k)
                            if distributed_plan_legal(
                                spec, shape, decomp, k, "pallas", swp,
                                vl, m, t0, n_devices, steps=steps,
                                remainder=p.remainder)]
                        pool = _ttile_fanout(spec, shape, variants,
                                             steps)
                        if swp == "resident":
                            # overlapped twin of every resident variant
                            # whose shard can host the boundary region
                            pool += [
                                dataclasses.replace(p, overlap=True)
                                for p in pool
                                if distributed_plan_legal(
                                    spec, shape, decomp, k, "pallas",
                                    swp, vl, m, t0, n_devices,
                                    steps=steps, remainder=p.remainder,
                                    ttile=p.ttile, overlap=True)]
                        cands += pool
    return cands


def _pallas_candidates(spec: stencils.StencilSpec, shape: tuple[int, ...],
                       steps: int | None,
                       budget_gate: bool = False) -> list[StencilPlan]:
    if budget_gate and jax.default_backend() != "tpu" and \
            int(np.prod(shape)) > INTERPRET_MAX_POINTS:
        return []          # interpret-mode measurement too costly off-TPU
    n0 = shape[0]
    cands: list[StencilPlan] = []
    if spec.ndim == 1:
        t0s: list[int | None] = [None]
    else:
        t0s = _pipeline_tiles(spec, n0, int(np.prod(shape[1:])))[:_MAX_T0]
    for vl, m in _pallas_pairs(shape[-1], spec.r, _kernel_lanes()):
        for t0 in t0s:
            for sweep in ("resident", "roundtrip"):
                if not pallas_plan_legal(spec, shape, vl, m, t0, sweep):
                    continue
                for k in _KS:
                    plan = StencilPlan(scheme="transpose", k=k, vl=vl, m=m,
                                       t0=t0, backend="pallas", sweep=sweep)
                    variants = [
                        p for p in _with_remainder(plan, steps, k)
                        if pallas_plan_legal(
                            spec, shape, vl, m, t0, sweep, k=k,
                            steps=steps, remainder=p.remainder)]
                    cands += _ttile_fanout(spec, shape, variants, steps)
    return cands


def candidate_plans(spec: stencils.StencilSpec, shape: Sequence[int],
                    dtype=jnp.float32, backend: str = "auto",
                    steps: int | None = None,
                    n_devices: int | None = None) -> list[StencilPlan]:
    """Every legal StencilPlan for (spec, shape, dtype, backend).

    ``backend="auto"`` pools the jnp, Pallas, mxu (banded-operator
    matrixization, gated by :func:`mxu_plan_legal`) and — on a ≥2-device
    host — distributed candidates into one list (the unified
    cross-backend search; ``n_devices`` overrides the visible device
    count, mostly for tests).  When ``steps`` is given, k>1 candidates whose block size
    does not divide it fan out along the remainder-policy axis (see
    :func:`_with_remainder`); without ``steps`` the canonical variants
    cover any step count via the ``fused`` fallback in
    ``StencilProblem.run``."""
    shape = tuple(shape)
    n = shape[-1]

    if backend == "auto":
        return (candidate_plans(spec, shape, dtype, "jnp", steps)
                + _pallas_candidates(spec, shape, steps, budget_gate=True)
                + _mxu_candidates(spec, shape, dtype, steps,
                                  n_devices=n_devices)
                + _distributed_candidates(spec, shape, steps,
                                          n_devices=n_devices,
                                          budget_gate=True))
    if backend == "pallas":
        return _pallas_candidates(spec, shape, steps)
    if backend == "mxu":
        return _mxu_candidates(spec, shape, dtype, steps,
                               n_devices=n_devices)
    if backend == "distributed":
        cands = _distributed_candidates(spec, shape, steps,
                                        n_devices=n_devices)
        if cands:
            return cands
        # single-device fallback (explicit request only): the legacy
        # no-decomp plans, run on a 1-device mesh (ring wraps locally)
        for k in _KS:
            cands += _with_remainder(
                StencilPlan(scheme="fused", k=k, backend="distributed"),
                steps, k)
        return cands
    if backend != "jnp":
        raise ValueError(f"unknown backend {backend!r}")

    # jnp backend -----------------------------------------------------------
    cands = []
    # single-step schemes
    for scheme in ("fused", "reorg", "multiload"):
        cands.append(StencilPlan(scheme=scheme, k=1))
    if n % min(_VLS) == 0:
        cands.append(StencilPlan(scheme="dlt", k=1, vl=min(_VLS)))
    for vl, m in _layout_pairs(n, spec.r):
        cands.append(StencilPlan(scheme="transpose", k=1, vl=vl, m=m))
    # unroll-and-jam (fused multistep — scheme inert on the k>1 jnp path;
    # the remainder policies coincide there too, so no native variant)
    for k in _KS[1:]:
        cands += _with_remainder(StencilPlan(scheme="transpose", k=k),
                                 steps, k, native_ok=False)
    # tessellation: tiles must divide the grid with room for the halo ramp
    from repro.core.tessellate import fit_tile
    for h in (2, 4):
        tile = fit_tile(spec, shape, h, strict=True)
        if tile is not None:
            cands += _with_remainder(
                StencilPlan(scheme="fused", k=1, tiling="tessellate",
                            tile=tile, height=h),
                steps, h)
    return cands


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuneResult:
    key: str
    plan: StencilPlan
    seconds_per_step: float
    n_candidates: int
    n_measured: int
    cached: bool                       # True: served from the plan cache
    measurements: list[dict] = dataclasses.field(default_factory=list)
    n_pruned_static: int = 0           # survivors the static audit rejected
    audit_seconds: float = 0.0         # wall time spent auditing survivors
    pruned: list = dataclasses.field(default_factory=list)  # [(plan, names)]
    failures: list = dataclasses.field(default_factory=list)  # [(plan, err)]


def _default_timer(fn: Callable[[], jax.Array], plan: StencilPlan) -> float:
    return bench(fn, warmup=1, iters=2, min_time_s=0.05)


def _uses_pallas_kernels(plan: StencilPlan) -> bool:
    return plan.backend == "pallas" or (plan.backend == "distributed"
                                        and plan.scheme == "transpose")


def _rank_time(spec, shape, itemsize, plan, steps, constants=None) -> float:
    t = estimate_plan_time(spec, shape, itemsize, plan, steps=steps,
                           constants=constants)
    if _uses_pallas_kernels(plan) and jax.default_backend() != "tpu":
        t *= INTERPRET_PENALTY
    return t


def _auto_measure_steps(steps: int | None) -> int:
    """Measurement window.  Tuning cost must not scale with the run's
    step count: a window congruent to ``steps`` mod every candidate block
    size (``_BLOCK_LCM + steps % _BLOCK_LCM``) exercises the identical
    remainder handling, so it ranks the same candidates at a fraction of
    the cost of timing the full run."""
    if steps is None:
        return _MEASURE_STEPS
    return min(steps, _BLOCK_LCM + steps % _BLOCK_LCM)


def _stratify(survivors: list[StencilPlan], ranked: list[StencilPlan]):
    """Ensure every backend present in the ranked pool keeps at least one
    measured candidate (its best-ranked one)."""
    have = {p.backend for p in survivors}
    for p in ranked:
        if p.backend not in have:
            survivors.append(p)
            have.add(p.backend)
    return survivors


def _audit_survivors(problem, survivors, steps):
    """Static plan audit — the fail-closed gate in front of the
    measurement loop.  Each survivor's program is traced abstractly (no
    execution) and checked against the invariant registry
    (:mod:`repro.analysis`); a plan with any violation is pruned with
    the violation named and is NEVER timed.  ``REPRO_PLAN_AUDIT=0``
    disables the gate (debug escape hatch).

    Returns ``(kept, pruned, seconds)`` where ``pruned`` is a list of
    ``(plan, violation-name tuple)`` pairs.
    """
    if os.environ.get("REPRO_PLAN_AUDIT", "1") == "0":
        return survivors, [], 0.0
    from repro import analysis     # lazy: analysis imports core.api
    t0 = time.perf_counter()
    kept, pruned = [], []
    for plan in survivors:
        report = analysis.audit_plan(problem, plan, steps=steps)
        if report.ok:
            kept.append(plan)
        else:
            names = report.violation_names()
            pruned.append((plan, names))
            logger.warning("candidate %s statically invalid, never "
                           "measured: %s", plan, ", ".join(sorted(set(names))))
    return kept, pruned, time.perf_counter() - t0


def tune(problem, backend: str = "auto", steps: int | None = None,
         cache_path: str | None = None, timer=None, max_measure: int = 8,
         measure_steps: int | None = None, force: bool = False,
         calibrate_samples: bool | None = None) -> TuneResult:
    """Resolve the best plan for ``problem`` (a StencilProblem).

    ``backend="auto"`` searches the jnp and Pallas pools together (the
    cross-backend search); a concrete backend restricts the pool.
    ``steps`` makes the plan (and its cache key) specific to that step
    count — remainder policies are enumerated and measured with the real
    remainder handling (see the module docstring).

    Cache hit → returns immediately without measuring.  Miss (or
    ``force=True``) → enumerate, roofline-prune to ``max_measure``
    (backend-stratified: >=1 candidate of each backend in the pool is
    always measured), measure each survivor with ``timer(fn, plan)``
    (seconds per ``measure_steps`` steps), persist the winner under a
    key carrying the code fingerprint (stale-proof, see
    :func:`plan_key`).

    ``calibrate_samples`` controls whether the measured samples feed the
    persistent roofline calibration (:mod:`repro.roofline.calibrate`).
    Default: only when the REAL wall-clock timer runs — an injected
    ``timer`` (stubs, simulators) would poison the monotone-ratchet
    constants with fake throughputs that can never be un-learned.
    """
    spec = problem.spec
    if calibrate_samples is None:
        calibrate_samples = timer is None
    steps = normalize_steps(steps)
    key = plan_key(spec.name, problem.shape, problem.dtype, backend,
                   steps=steps)
    cache = get_cache(cache_path)
    if not force:
        cache.refresh()
        hit = cache.get(key)
        if hit is not None:
            return TuneResult(key=key, plan=plan_from_dict(hit["plan"]),
                              seconds_per_step=hit["seconds_per_step"],
                              n_candidates=hit.get("n_candidates", 0),
                              n_measured=hit.get("n_measured", 0),
                              cached=True)

    timer = timer or _default_timer
    cands = candidate_plans(spec, problem.shape, problem.dtype, backend,
                            steps=steps)
    if not cands:
        raise ValueError(f"no legal plans for {key}")
    itemsize = jnp.dtype(problem.dtype).itemsize
    # ranking constants: per-device-kind peaks fitted from earlier
    # measured runs (static TPU-v5e numbers until samples exist)
    constants = calibrate.load_constants(device=device_kind(),
                                         cache_path=cache.path)
    ranked = sorted(cands, key=lambda p: _rank_time(
        spec, problem.shape, itemsize, p, steps, constants))
    survivors = _stratify(ranked[:max_measure], ranked)
    # the historical fixed default must stay in the pool so the tuned plan
    # can never lose to it
    default = problem.default_plan()
    if backend in ("jnp", "auto") and default not in survivors:
        survivors.append(default)

    measure_steps = measure_steps or _auto_measure_steps(steps)
    # static audit gate: prove the layout invariants on each survivor's
    # traced program (the very program the timer would run) BEFORE any
    # measurement — a statically-invalid candidate is never timed.
    survivors, pruned, audit_seconds = _audit_survivors(
        problem, survivors, measure_steps)
    if not survivors:
        raise RuntimeError(
            f"every candidate for {key} is statically invalid: "
            + "; ".join(f"{p}: {', '.join(sorted(set(n)))}"
                        for p, n in pruned))
    x = problem.init(seed=0)
    measurements, failures = [], []
    best_plan, best_t = None, float("inf")
    for plan in survivors:
        fn = lambda p=plan: problem.run(x, measure_steps, p)
        try:
            t = float(timer(fn, plan)) / measure_steps
        except Exception as e:   # recorded, and the search goes on
            logger.warning("candidate %s failed: %s", plan, e)
            failures.append((plan, f"{type(e).__name__}: {e}"))
            continue
        measurements.append({"plan": plan_to_dict(plan),
                             "seconds_per_step": t})
        logger.info("measured %s: %.3es/step", plan, t)
        if t < best_t:
            best_plan, best_t = plan, t
    if best_plan is None:
        raise RuntimeError(f"every candidate failed for {key}: "
                           + "; ".join(f"{p}: {e}" for p, e in failures))

    # feed the roofline calibrator: every measured (modeled-terms, wall
    # time) pair tightens the per-device-kind throughput peaks — the max
    # ratchet ignores slow (e.g. interpret-mode) samples, so pruning
    # sharpens monotonically as tuning runs accumulate.  Only real
    # wall-clock measurements qualify (see the docstring).
    if calibrate_samples:
        # small grids may be cache-resident: their apparent bandwidth is
        # cache, not HBM — exclude them from the hbm_bw fit (bytes=0).
        # The terms are PER DEVICE, so the gate is on the per-shard
        # working set: a 128 MB global grid split 8 ways is 16 MB/shard.
        working_set = 2.0 * float(np.prod(problem.shape)) * itemsize
        samples = []
        for row in measurements:
            p = plan_from_dict(row["plan"])
            f, b, c = plan_terms(spec, problem.shape, itemsize, p, steps)
            shards = float(np.prod(p.decomp)) if p.decomp else 1.0
            fit_bw = working_set / shards \
                >= calibrate.MIN_BANDWIDTH_WORKING_SET
            sample = {"flops": f, "bytes": b if fit_bw else 0.0,
                      "coll_bytes": c,
                      "seconds": row["seconds_per_step"]}
            if p.backend == "mxu":
                # mxu terms are MATMUL flops — they fit the separate
                # peak_flops_mxu ratchet, never the VPU peak
                sample["mxu_flops"], sample["flops"] = sample["flops"], 0.0
            samples.append(sample)
        try:
            calibrate.record_samples(samples, device=device_kind(),
                                     cache_path=cache.path)
        except OSError as e:                  # calibration is best-effort
            logger.warning("roofline calibration not persisted: %s", e)

    record = {"plan": plan_to_dict(best_plan), "seconds_per_step": best_t,
              "fingerprint": code_fingerprint(),
              "n_candidates": len(cands), "n_measured": len(measurements),
              "n_pruned_static": len(pruned),
              "audit_seconds": audit_seconds,
              "pruned": [{"plan": plan_to_dict(p),
                          "violations": sorted(set(n))} for p, n in pruned],
              "failures": [{"plan": plan_to_dict(p), "error": e}
                           for p, e in failures],
              "measurements": measurements}
    cache.put(key, record)
    cache.save()
    logger.info("tuned %s → %s (%.3es/step, %d measured of %d, "
                "%d pruned statically in %.0f ms)", key,
                best_plan, best_t, len(measurements), len(cands),
                len(pruned), audit_seconds * 1e3)
    return TuneResult(key=key, plan=best_plan, seconds_per_step=best_t,
                      n_candidates=len(cands),
                      n_measured=len(measurements), cached=False,
                      measurements=measurements,
                      n_pruned_static=len(pruned),
                      audit_seconds=audit_seconds,
                      pruned=list(pruned), failures=failures)


def best_plan(problem, backend: str = "auto", steps: int | None = None,
              cache_path: str | None = None, **kw) -> StencilPlan:
    return tune(problem, backend=backend, steps=steps,
                cache_path=cache_path, **kw).plan


def plan_batch_invariant(plan: StencilPlan) -> bool:
    """The batch-invariance gate: may a plan tuned for the *unbatched*
    (stencil, shape, dtype) signature serve a leading-batch-axis run
    (``StencilProblem.run_batched``) unchanged?

    Plan keys deliberately carry NO batch-size component — the serving
    batcher coalesces requests at whatever slot count admission picks,
    and a per-batch-size key would fragment the cache and force one
    tuning run per slot count for a plan whose execution is identical at
    every batch size.  That reuse is sound because:

    * jnp / pallas plans: ``run_batched`` vmaps the WHOLE single-grid
      program; ``vmap`` adds the batch as an outer loop/grid dimension
      and leaves the (nb, m, vl) layout axes, the k-blocking, the
      temporal tiling and the sweep schedule untouched.  Every legality
      gate (:func:`pallas_plan_legal`, :func:`ttile_plan_legal`) is a
      predicate of the unbatched shape, which the batch axis never
      enters — so a legal plan stays legal, and per-element results are
      bit-identical to ``B`` unbatched runs (pinned in
      tests/test_serve_batcher.py).
    * distributed plans: the mesh decomposition consumes the physical
      devices, so ``run_batched`` runs elements *sequentially* through
      the same cached shard_map program — trivially the unbatched
      execution, batch-size-invariant by construction.  (The batcher
      additionally claims the mesh exclusively for these.)

    * mxu plans: the banded operator is a function of (spec, vl, m,
      depth) ONLY — its matrix shapes never absorb the batch;
      ``run_batched`` vmaps the whole program and the batch rides as an
      outer dot_general dimension.  One rounding-level caveat: XLA may
      re-block the larger batched matmul, reassociating the f32
      accumulation by a few ulp versus the unbatched gemm (both
      roundings correct — pinned at tight tolerance, not bitwise, in
      tests/test_serve_batcher.py).  Distributed mxu plans carry a
      ``decomp`` and serve through the same sequential mesh-exclusive
      path as other distributed plans via the batcher.

    The gate exists so a future backend whose layout DOES depend on the
    batch (e.g. folding the batch into the lane axis, or a matrixization
    whose matrix shapes absorb B) has a place to say so — ``run_batched``
    refuses such plans instead of silently serving a shape the tuner
    never measured.  Unknown backends fail closed."""
    return plan.backend in ("jnp", "pallas", "mxu", "distributed")


def cached_plan(problem, backend: str = "auto", steps: int | None = None,
                cache_path: str | None = None,
                generic_fallback: bool = True) -> StencilPlan | None:
    """Cache lookup only — never measures.  The serving path uses this so a
    cold cache falls back to the static default instead of blocking a
    request on a tuning run.  A per-``steps`` key is tried first, then
    (unless ``generic_fallback=False``) the generic (``steps=None``) key
    tuned for any step count."""
    cache = get_cache(cache_path)
    cache.refresh()
    steps = normalize_steps(steps)
    keys = [plan_key(problem.spec.name, problem.shape, problem.dtype,
                     backend, steps=steps)]
    if steps is not None and generic_fallback:
        keys.append(plan_key(problem.spec.name, problem.shape,
                             problem.dtype, backend, steps=None))
    for key in keys:
        hit = cache.get(key)
        if hit is not None:
            return plan_from_dict(hit["plan"])
    return None
