"""Public stencil API — the paper's technique as a composable feature.

    from repro.core.api import StencilProblem
    p = StencilProblem("2d5p", shape=(512, 512))
    y = p.run(x, steps=100, plan="auto")

Plans compose the paper's three pieces:
  scheme      — vectorization layout per step: multiload | reorg | dlt |
                transpose (paper's) | fused
  k           — time unroll-and-jam factor (in-register / in-VMEM multistep)
  tiling      — none | tessellate (H=k·…, tile=W)
  backend     — jnp | pallas (kernels/) | mxu (banded-operator matmul,
                core/matrixize.py) | distributed (shard_map halo)
  remainder   — how steps % k leftovers run: "fused" (single steps on the
                same backend) | "native" (one k=remainder block)
  sweep       — sweep engine (pallas + distributed-pallas): "resident"
                (one program for the whole run, transpose-layout held
                across every sweep/exchange, zero wrap-pad copies) |
                "roundtrip" (legacy per-sweep pad/transpose/crop)
  ttile       — temporal tile (resident engines): ttile consecutive
                k-blocks fuse into ONE depth-ttile·k trapezoid launch,
                cutting HBM round-trips (and distributed ghost
                exchanges) to one per ttile·k steps at the price of a
                deeper halo slope ttile·k·r
  decomp      — distributed plans: per-spatial-axis shard counts, e.g.
                (8,) or (4, 2); the mesh decomposition axis the unified
                autotuner searches jointly with k and the engine.  On the
                distributed backend ``scheme`` picks the local engine:
                "transpose" → the shard-resident Pallas kernels, anything
                else → fused jnp steps on the halo-extended shard.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import stencils, vectorize, unroll_jam, tessellate


def sweep_schedule(k: int, steps: int | None,
                   remainder: str = "fused", ttile: int = 1
                   ) -> tuple[list[tuple[int, int]], int]:
    """The (depth, n_launches) blocks a ``steps``-long k-blocked run
    executes — each entry is ``n`` kernel launches (halo exchanges, on
    the distributed backend) of ``depth`` time steps apiece: the
    ``ttile``-grouped main k-blocks, the ungrouped k-block leftovers,
    then the remainder policy ("native": one k=rem sweep; "fused": rem
    single-step sweeps).  ``steps=None`` (ranking without a step count)
    yields one canonical depth-``ttile·k`` block.  Returns (chunks,
    total steps to amortize over).

    ``ttile`` is the temporal-tile factor: ``ttile`` consecutive
    k-blocks fuse into ONE depth-``ttile·k`` launch, so the grid makes
    one HBM round-trip (one ghost exchange) per ``ttile·k`` steps
    instead of per ``k``.  The remainder semantics stay defined mod
    ``k`` — ``ttile`` only regroups the main k-blocks, so any
    (steps, k, remainder) run is bit-identical at every ttile.

    Single source of truth for the sweep decomposition — shared by the
    resident single-device engine (``kernels/ops._sweep_periodic_impl``),
    the distributed runtime (``distributed/multistep.make_run`` builds
    its program from these chunks) and the roofline's per-chunk
    accounting (``roofline/stencil._distributed_terms``), so the model
    can never silently charge a schedule the runtime stopped executing.
    ``StencilProblem._chunked`` below realizes the same decomposition in
    aggregated (n_steps, k) form for the legacy single-device backends."""
    k = max(k, 1)
    ttile = max(ttile, 1)
    if steps is None:
        return [(k * ttile, 1)], k * ttile
    n_main, rem = divmod(steps, k)
    n_tt, tt_rem = divmod(n_main, ttile)
    chunks = []
    if n_tt:
        chunks.append((k * ttile, n_tt))
    if tt_rem:
        chunks.append((k, tt_rem))
    if rem:
        chunks.append((rem, 1) if remainder == "native" else (1, rem))
    return chunks, steps


@dataclasses.dataclass(frozen=True)
class StencilPlan:
    scheme: str = "transpose"
    k: int = 2
    tiling: str = "none"           # none | tessellate
    tile: tuple[int, ...] | None = None
    height: int | None = None      # tessellation height (defaults to k)
    vl: int = 8
    m: int | None = None
    backend: str = "jnp"           # jnp | pallas | mxu | distributed
    t0: int | None = None          # pallas n-D pipeline tile (rows/grid step)
    remainder: str = "fused"       # fused | native — steps % k policy
    sweep: str = "resident"        # resident | roundtrip — pallas engine
    decomp: tuple[int, ...] | None = None   # distributed: shards per axis
    ttile: int = 1                 # temporal tile: k-blocks per HBM/ghost
    #                                round-trip (resident engines only)
    overlap: bool = False          # distributed resident: hide the halo
    #                                ring behind interior compute
    #                                (interior/boundary split; bitwise-
    #                                identical to the serialized exchange)


class StencilProblem:
    def __init__(self, name: str, shape: Sequence[int], dtype=jnp.float32):
        self.spec = stencils.make(name)
        assert len(shape) == self.spec.ndim, (shape, self.spec.ndim)
        self.shape = tuple(shape)
        self.dtype = dtype
        # jitted batched runners, one per (batch, steps, plan) — see
        # run_batched (the serving batcher's compile-once entry point)
        self._batched_fns: dict[tuple, object] = {}

    # ------------------------------------------------------------------
    def init(self, seed: int = 0) -> jax.Array:
        key = jax.random.PRNGKey(seed)
        return jax.random.normal(key, self.shape, self.dtype)

    def reference(self, x: jax.Array, steps: int, bc: str = "periodic"):
        return stencils.apply_steps(self.spec, x, steps, bc)

    # ------------------------------------------------------------------
    def run(self, x: jax.Array, steps: int,
            plan: StencilPlan | str = "auto") -> jax.Array:
        """Advance ``x`` by ``steps`` Jacobi steps (periodic BC) under
        ``plan``.

        plan:
          * a ``StencilPlan`` — executed as given;
          * ``"default"`` — the static fallback plan (no measurement);
          * ``"auto"`` — resolved by the unified cross-backend autotuner
            (:mod:`repro.core.autotune`): legal jnp AND Pallas candidates
            are enumerated in one pool, roofline-pruned, the best few are
            *timed on this device* for THIS step count, and the winner is
            persisted to the JSON plan cache (path from the
            ``REPRO_PLAN_CACHE`` env var, default
            ``~/.cache/repro/plan_cache.json``; see the autotune module
            docstring for the file format).  Later runs of the same
            (stencil, shape, dtype, backend, device-kind, steps,
            code-fingerprint) signature hit the cache and skip
            re-measurement.

        Any plan is valid for any ``steps``: when k (or the tessellation
        height) does not divide ``steps``, the remainder runs according to
        ``plan.remainder`` — single steps ("fused") or one shorter
        k=remainder block ("native") on the same backend.
        """
        with TraceAnnotation("repro.run"):
            if isinstance(plan, str):
                with TraceAnnotation("repro.plan"):
                    plan = self._resolve(plan, steps)
            with TraceAnnotation("repro.dispatch"):
                return self._execute(x, steps, plan)

    def _execute(self, x: jax.Array, steps: int,
                 plan: StencilPlan) -> jax.Array:
        """Run ``steps`` under a resolved ``plan`` on its engine."""
        assert isinstance(plan, StencilPlan)
        if plan.ttile > 1 and not (
                plan.backend in ("distributed", "mxu")
                or (plan.backend == "pallas" and plan.sweep == "resident")):
            raise ValueError(
                f"ttile={plan.ttile} requires a resident sweep engine "
                "(backend='pallas' with sweep='resident', backend='mxu', "
                "or backend='distributed'); the legacy paths round-trip "
                "every sweep, so there is nothing to temporally tile")
        if plan.overlap and not (plan.backend == "distributed"
                                 and plan.scheme == "transpose"
                                 and plan.sweep == "resident"):
            raise ValueError(
                "overlap=True requires the distributed shard-resident "
                "pallas engine (backend='distributed', scheme='transpose', "
                "sweep='resident'); other paths have no halo ring to hide "
                "behind interior compute")
        if plan.backend == "mxu":
            # banded-operator engine: every depth-d chunk is ONE
            # dot_general against A^d (core/matrixize.py).  With a
            # decomp the same operator runs shard-resident over the
            # distributed ghost codec.
            vl = plan.vl if plan.m is not None else None
            if plan.decomp is not None:
                from repro.distributed import multistep as dms
                return dms.distributed_run(
                    self.spec, x, steps, k=plan.k, engine="mxu",
                    shards=plan.decomp, sweep=plan.sweep,
                    remainder=plan.remainder, vl=vl, m=plan.m,
                    t0=plan.t0, ttile=plan.ttile)
            from repro.kernels import ops
            return ops.stencil_sweep_mxu(
                self.spec, x, steps, k=plan.k, vl=vl, m=plan.m,
                remainder=plan.remainder, ttile=plan.ttile)
        if plan.backend == "pallas":
            from repro.kernels import ops
            # m=None means "kernel auto-picks the native tile" (vl=128 on
            # TPU); tuner-built pallas plans always carry an explicit
            # (vl, m) pair and those are honored.
            vl = plan.vl if plan.m is not None else None
            if plan.sweep == "resident":
                # layout-resident engine: ONE program for all steps — the
                # (ttile-grouped) k-blocked sweeps AND the steps % k
                # remainder are fused inside (no _chunked round-trips
                # between sweeps).
                return ops.stencil_sweep_periodic(
                    self.spec, x, steps, k=plan.k, vl=vl, m=plan.m,
                    t0=plan.t0, remainder=plan.remainder,
                    ttile=plan.ttile)
            if plan.sweep != "roundtrip":
                raise ValueError(f"unknown sweep engine {plan.sweep!r}")
            return self._chunked(
                x, steps, plan.k,
                lambda v, n, k: ops.stencil_run_periodic(
                    self.spec, v, n, k=k, vl=vl, m=plan.m, t0=plan.t0),
                remainder=plan.remainder)
        if plan.backend == "distributed":
            from repro.distributed import multistep as dms
            # scheme picks the local engine; the remainder policy is fused
            # into the single shard_map program (no _chunked round-trips —
            # a shard-resident plan transposes exactly once per run).
            engine = "pallas" if plan.scheme == "transpose" else "jnp"
            vl = plan.vl if plan.m is not None else None
            return dms.distributed_run(
                self.spec, x, steps, k=plan.k, engine=engine,
                shards=plan.decomp, sweep=plan.sweep,
                remainder=plan.remainder, vl=vl, m=plan.m, t0=plan.t0,
                ttile=plan.ttile, overlap=plan.overlap)
        if plan.tiling == "tessellate":
            h = plan.height or plan.k
            tile = plan.tile or self._default_tile(h)

            def step(v, n, k):
                if k == 1:          # remainder: fused single steps
                    return vectorize.run_scheme("fused", self.spec, v, n,
                                                plan.vl, plan.m)
                return tessellate.tessellate_run(
                    self.spec, v, n, tile, k, inner=plan.scheme
                    if plan.scheme in ("fused", "transpose", "dlt")
                    else "fused", vl=plan.vl)
            return self._chunked(x, steps, h, step,
                                 remainder=plan.remainder)
        if plan.k > 1:
            def step(v, n, k):
                for _ in range(n // k):
                    v = unroll_jam.multistep_fused(self.spec, v, k)
                return v
            return self._chunked(x, steps, plan.k, step,
                                 remainder=plan.remainder)
        return vectorize.run_scheme(plan.scheme, self.spec, x, steps,
                                    plan.vl, plan.m)

    def run_batched(self, xb: jax.Array, steps: int,
                    plan: StencilPlan | str = "auto") -> jax.Array:
        """Advance a BATCH of grids — ``xb``: (B,) + ``self.shape`` — by
        ``steps`` under ONE shared program per (B, steps, plan).

        This is the continuous-batching serving entry: the whole
        single-grid run (transpose into the (nb, m, vl) layout, every
        sweep of the ``sweep_schedule``, untranspose) is ``vmap``-ped
        over the leading batch axis and jitted ONCE, so N coalesced
        requests share one transpose-in/untranspose and one compiled
        executable instead of paying per-request dispatch — and nothing
        recompiles after the first call at a given batch size (the
        batcher pads to a fixed slot-count set for exactly this reason).
        Results are bit-identical to ``B`` independent :meth:`run` calls:
        ``vmap`` adds the batch as an outer dimension and leaves the
        per-element arithmetic untouched (the batch-invariance contract,
        see :func:`repro.core.autotune.plan_batch_invariant`; pinned in
        tests/test_serve_batcher.py).  The mxu engine is the one
        rounding-level exception: XLA may re-block the batched matmul
        (more rows → different gemm tiling), reassociating the f32
        accumulation by a few ulp — both roundings correct, pinned at
        tight tolerance rather than bitwise.

        Mesh-decomposed plans are the exception — ``backend=
        "distributed"`` and any plan with a ``decomp`` axis (e.g. a
        distributed mxu plan): their mesh decomposition already consumes
        the physical devices, so batch elements run sequentially through
        the same cached shard_map program (the batcher claims the mesh
        exclusively while this happens).
        """
        plan = self._batched_plan(plan, steps)
        xb = jnp.asarray(xb)
        if xb.shape[1:] != self.shape:
            raise ValueError(f"run_batched expects (B,) + {self.shape}, "
                             f"got {xb.shape}")
        if plan.backend == "distributed" or plan.decomp is not None:
            # the mesh holds the spatial decomposition; elements reuse the
            # cached shard-resident program one after another.
            return jnp.stack([self.run(xb[i], steps, plan)
                              for i in range(xb.shape[0])])
        key = (xb.shape[0], steps, plan)
        fn = self._batched_fns.get(key)
        if fn is None:
            fn = jax.jit(jax.vmap(lambda v: self.run(v, steps, plan)))
            self._batched_fns[key] = fn
        with TraceAnnotation("repro.dispatch"):
            return fn(xb)

    def run_batched_parts(self, xs, steps: int,
                          plan: StencilPlan | str = "auto") -> list:
        """Per-slot variant of :meth:`run_batched` for the serving hot
        path: takes a sequence of B same-shape grids and returns the B
        advanced grids as a list, with the leading-axis stack AND the
        per-slot unstack folded INTO the single jitted program.  One
        dispatch total — ``run_batched`` on a host-stacked batch pays a
        ``jnp.stack`` dispatch going in and B slice dispatches coming
        out, which at serving batch sizes costs more than the sweep
        itself.  Arithmetic is the same vmapped program, so results stay
        bit-identical to per-element :meth:`run` calls."""
        xs = [jnp.asarray(x) for x in xs]
        for x in xs:
            if x.shape != self.shape:
                raise ValueError(f"run_batched_parts expects grids of "
                                 f"shape {self.shape}, got {x.shape}")
        plan = self._batched_plan(plan, steps)
        if plan.backend == "distributed" or plan.decomp is not None:
            return [self.run(x, steps, plan) for x in xs]
        key = (len(xs), steps, plan, "parts")
        fn = self._batched_fns.get(key)
        if fn is None:
            run = lambda v: self.run(v, steps, plan)  # noqa: E731
            fn = jax.jit(
                lambda parts: tuple(jax.vmap(run)(jnp.stack(parts))))
            self._batched_fns[key] = fn
        with TraceAnnotation("repro.dispatch"):
            return list(fn(tuple(xs)))

    def _batched_plan(self, plan: StencilPlan | str,
                      steps: int) -> StencilPlan:
        """Resolve a plan argument for the batched entries and enforce
        the batch-invariance gate."""
        if isinstance(plan, str):
            with TraceAnnotation("repro.plan"):
                plan = self._resolve(plan, steps)
        assert isinstance(plan, StencilPlan)
        from repro.core import autotune
        if not autotune.plan_batch_invariant(plan):
            raise ValueError(f"plan {plan} is not batch-invariant; "
                             "it cannot serve a batched run unchanged")
        return plan

    def _resolve(self, plan: str, steps: int) -> StencilPlan:
        """The plan a string names: ``"auto"`` (the tuner's, see
        :meth:`run`) or ``"default"``."""
        if plan == "auto":
            from repro.core import autotune
            return autotune.best_plan(self, steps=steps)
        if plan == "default":
            return self.default_plan()
        raise ValueError(f"unknown plan {plan!r}; expected 'auto', "
                         f"'default' or a StencilPlan")

    def _chunked(self, x: jax.Array, steps: int, k: int, step,
                 remainder: str = "fused") -> jax.Array:
        """Run ``steps`` as k-blocked sweeps plus a remainder:
        step(x, n_steps, k) advances x by n_steps in k-step blocks.

        remainder="fused"  → leftover steps run one at a time (k=1);
        remainder="native" → leftover steps run as ONE k=remainder block
        (one extra pipelined sweep / one shorter tessellation round)."""
        main = steps - steps % k
        if main:
            x = step(x, main, k)
        rem = steps - main
        if rem:
            if remainder == "native":
                x = step(x, rem, rem)
            elif remainder == "fused":
                x = step(x, rem, 1)
            else:
                raise ValueError(f"unknown remainder policy {remainder!r}")
        return x

    def default_plan(self) -> StencilPlan:
        """The static pre-autotuner plan — also the baseline every tuning
        run measures against (the tuned pick can never be slower)."""
        return StencilPlan(scheme="transpose", k=2, vl=8)

    def _default_tile(self, h: int) -> tuple[int, ...]:
        return tessellate.fit_tile(self.spec, self.shape, h)

    # ------------------------------------------------------------------
    def model_flops(self, steps: int) -> int:
        return stencils.model_flops(self.spec, self.shape, steps)

    def model_bytes(self, steps: int, k: int = 1) -> int:
        return stencils.model_bytes(
            self.spec, self.shape, steps,
            itemsize=jnp.dtype(self.dtype).itemsize, k=k)
