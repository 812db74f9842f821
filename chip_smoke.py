#!/usr/bin/env python3
"""Smoke test of the stencil engine on a TPU: one process, no CPU fallback.

    python chip_smoke.py                # one chip: phases 1-3
    python chip_smoke.py --four-chips   # four chips: the sharded path alone

Phase 1 runs explicit plans through ``StencilProblem.run`` at the paper's
sizes, and checks that every Pallas program holds a Mosaic kernel
(``tpu_custom_call``).  Phase 2 runs ``plan="auto"``, the measured plan
search.  Phase 3 serves requests through ``StencilService``.  With
``--four-chips`` only the sharded 2-D path runs, against its one-chip run.

Inputs come from ``StencilProblem.init(seed)``.  Every result is checked
against ``stencils.apply_steps``, the f32 oracle, within ``TOL`` (max abs
error; bf16 inputs are cast to f32 for the oracle).  Each case prints one
line, and the first failure exits non-zero.  The last line is one JSON
object naming the device.  Times printed are smoke times on this run
(compilation included where stated), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import autotune, stencils  # noqa: E402
from repro.core.api import StencilPlan, StencilProblem  # noqa: E402

STEPS, K, SEED = 16, 2, 0
TOL = {"float32": 1e-4, "bfloat16": 6.25e-2}
N1D = 10_244_096                 # the paper's 1-D size
N2D = (3072, 3072)               # the paper's 2-D size
N3D = (256, 256, 256)
N2D_FOUR = (6144, 6144)          # one 3072² shard per chip on a 2x2 mesh
SERVED_REQUESTS, TENANTS = 8, 2

F32, BF16 = jnp.float32, jnp.bfloat16


def resident(k: int = K, ttile: int = 1) -> StencilPlan:
    return StencilPlan(scheme="transpose", k=k, backend="pallas",
                       sweep="resident", ttile=ttile, m=None)


MXU = StencilPlan(scheme="transpose", k=K, backend="mxu", m=None)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def oracle(prob: StencilProblem, x, steps: int):
    spec = prob.spec
    return jax.jit(lambda v: stencils.apply_steps(
        spec, v.astype(jnp.float32), steps))(x)


def max_err(y, ref) -> float:
    return float(jnp.max(jnp.abs(y.astype(jnp.float32) - ref)))


def describe(plan: StencilPlan) -> str:
    out = f"{plan.backend}"
    if plan.backend == "pallas":
        out += f"/{plan.sweep}"
    elif plan.backend == "distributed":
        engine = "pallas" if plan.scheme == "transpose" else "jnp"
        out += f"/{engine}/{plan.sweep}"
    out += f" k={plan.k} ttile={plan.ttile}"
    if plan.m is not None:
        out += f" vl={plan.vl} m={plan.m}"
    if plan.t0 is not None:
        out += f" t0={plan.t0}"
    if plan.decomp is not None:
        out += f" decomp={plan.decomp} overlap={plan.overlap}"
    if plan.backend == "jnp":
        out += f" scheme={plan.scheme} tiling={plan.tiling}"
    return out


def run_case(label: str, prob: StencilProblem, plan: StencilPlan,
             kernel: bool) -> None:
    """Compile ``StencilProblem.run`` under ``plan``, run it, and check
    it against the oracle (and for a kernel plan, that Mosaic built it)."""
    dt = jnp.dtype(prob.dtype).name
    x = prob.init(SEED)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda v: prob.run(v, STEPS, plan)).lower(x).compile()
    t_compile = time.perf_counter() - t0
    custom = "tpu_custom_call" in compiled.as_text()
    check(custom or not kernel,
          f"{label}: no tpu_custom_call in the compiled program")
    t0 = time.perf_counter()
    y = jax.block_until_ready(compiled(x))
    t_run = time.perf_counter() - t0
    err = max_err(y, oracle(prob, x, STEPS))
    line = (f"case {label} {prob.spec.name} {prob.shape} {dt} "
            f"{describe(plan)} steps={STEPS}: max_err={err:.3e} "
            f"tol={TOL[dt]:.1e} tpu_custom_call={custom} "
            f"smoke_compile_s={t_compile:.2f} smoke_run_s={t_run:.4f}")
    check(np.isfinite(err) and err <= TOL[dt], f"{line} -> error over tol")
    print(line, flush=True)


def phase_explicit() -> None:
    cases = []
    for name in ("1d3p", "1d5p"):
        for tt in (1, 4):
            cases.append((name, (N1D,), F32, resident(ttile=tt)))
    cases.append(("1d3p", (N1D,), BF16, resident()))
    for name in ("2d5p", "2d9p"):
        for dt in (F32, BF16):
            for tt in (1, 2):
                cases.append((name, N2D, dt, resident(ttile=tt)))
    for name in ("3d7p", "3d27p"):
        cases.append((name, N3D, F32, resident()))
    for name, shape, dt, plan in cases:
        run_case("explicit", StencilProblem(name, shape, dt), plan,
                 kernel=True)
    for name, shape in (("1d3p", (N1D,)), ("2d5p", N2D)):
        run_case("explicit", StencilProblem(name, shape, F32), MXU,
                 kernel=False)
    prob = StencilProblem("2d5p", N2D, F32)
    run_case("default", prob, prob.default_plan(), kernel=False)


def phase_auto() -> None:
    prob = StencilProblem("2d5p", N2D, F32)
    t0 = time.perf_counter()
    res = autotune.tune(prob, steps=STEPS, force=True)
    t_tune = time.perf_counter() - t0
    for row in res.measurements:
        plan = autotune.plan_from_dict(row["plan"])
        print(f"auto measured {describe(plan)}: "
              f"smoke_s_per_step={row['seconds_per_step']:.4e}", flush=True)
    for plan, err in res.failures:
        print(f"auto FAILED {describe(plan)}: {err}", flush=True)
    for plan, names in res.pruned:
        print(f"auto PRUNED {describe(plan)}: {sorted(set(names))}",
              flush=True)
    check(not res.failures, f"auto: {len(res.failures)} candidate(s) failed")
    check(not res.pruned,
          f"auto: the audit pruned {len(res.pruned)} registry-legal plan(s)")
    backends = [row["plan"]["backend"] for row in res.measurements]
    check("pallas" in backends, f"auto: no pallas candidate measured "
          f"(measured {backends})")
    print(f"auto tuned {prob.spec.name} {prob.shape} float32: "
          f"{res.n_measured} measured of {res.n_candidates}, winner "
          f"{describe(res.plan)}, audit {res.audit_seconds:.2f}s, "
          f"smoke_tune_s={t_tune:.1f}", flush=True)
    x = prob.init(SEED)
    y = prob.run(x, STEPS, "auto")
    err = max_err(y, oracle(prob, x, STEPS))
    check(err <= TOL["float32"], f"auto: max_err={err:.3e} over tol")
    print(f"case auto 2d5p {prob.shape} float32 {describe(res.plan)} "
          f"steps={STEPS}: max_err={err:.3e} tol={TOL['float32']:.1e}",
          flush=True)


def phase_served() -> None:
    from repro.serve.engine import StencilService
    prob = StencilProblem("2d5p", N2D, F32)
    with StencilService() as svc:
        plan = svc.warm_async("2d5p", N2D, F32, steps=STEPS).result()
        print(f"served plan {describe(plan)}", flush=True)
        xs = [prob.init(SEED + 1 + i) for i in range(SERVED_REQUESTS)]
        t0 = time.perf_counter()
        futs = [svc.sweep_async("2d5p", x, STEPS, tenant=f"t{i % TENANTS}")
                for i, x in enumerate(xs)]
        outs = [f.result(timeout=900) for f in futs]
        t_serve = time.perf_counter() - t0
    for i, (x, y) in enumerate(zip(xs, outs)):
        want = prob.run(x, STEPS, plan)
        if plan.backend == "mxu":
            err = max_err(y, want.astype(jnp.float32))
            same = err <= TOL["float32"]
        else:
            same = bool(jnp.array_equal(y, want))
            err = max_err(y, want.astype(jnp.float32))
        ref_err = max_err(y, oracle(prob, x, STEPS))
        line = (f"case served #{i} tenant=t{i % TENANTS} 2d5p {N2D} "
                f"float32 {describe(plan)}: equal_to_run={same} "
                f"max_err_vs_oracle={ref_err:.3e}")
        check(same and ref_err <= TOL["float32"], f"{line} -> mismatch")
        print(line, flush=True)
    print(f"served {SERVED_REQUESTS} requests from {TENANTS} tenants: "
          f"smoke_wall_s={t_serve:.2f} (compilation included)", flush=True)


def phase_four_chips() -> None:
    from jax.sharding import NamedSharding

    from repro.distributed import halo
    from repro.distributed import multistep as dms
    prob = StencilProblem("2d5p", N2D_FOUR, F32)
    x = prob.init(SEED)
    single = jax.block_until_ready(prob.run(x, STEPS, resident()))
    err = max_err(single, oracle(prob, x, STEPS))
    check(err <= TOL["float32"], f"four-chips: one-chip run max_err={err}")
    print(f"case one-chip 2d5p {N2D_FOUR} float32 {describe(resident())}: "
          f"max_err={err:.3e} tol={TOL['float32']:.1e}", flush=True)
    plans = []
    for decomp in ((2, 2), (4, 1)):
        for overlap in (False, True):
            plans.append(StencilPlan(
                scheme="transpose", k=K, backend="distributed",
                sweep="resident", decomp=decomp, overlap=overlap, m=None))
    plans.append(StencilPlan(scheme="transpose", k=K, backend="mxu",
                             decomp=(2, 2), m=None))
    serialized = {}
    for plan in plans:
        mesh, names = dms.mesh_for_shards(plan.decomp)
        xs = jax.device_put(x, NamedSharding(
            mesh, halo.partition_spec(names, 2)))
        devices = {s.device for s in xs.addressable_shards}
        check(len(devices) == 4, f"four-chips {describe(plan)}: input "
              f"on {len(devices)} distinct devices")
        t0 = time.perf_counter()
        y = jax.block_until_ready(prob.run(xs, STEPS, plan))
        t_run = time.perf_counter() - t0
        diff = max_err(y, single)
        bitwise = bool(jnp.array_equal(y, single))
        line = (f"case four-chips 2d5p {N2D_FOUR} float32 {describe(plan)}"
                f": devices={len(devices)} max_diff_vs_one_chip={diff:.3e} "
                f"bitwise_vs_one_chip={bitwise} "
                f"smoke_first_run_s={t_run:.2f}")
        check(diff <= TOL["float32"], f"{line} -> differs from one chip")
        if plan.backend == "distributed":
            if plan.overlap:
                same = bool(jnp.array_equal(y, serialized[plan.decomp]))
                line += f" overlapped_bitwise_vs_serialized={same}"
                check(same, f"{line} -> overlap changed the result")
            else:
                serialized[plan.decomp] = y
        print(line, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded path on four chips, and only it")
    args = ap.parse_args(argv)
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); "
              "refusing to fall back", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    compile_cache.enable()
    # the plan cache, and the roofline calibration beside it, stay in the
    # checkout
    os.environ.setdefault("REPRO_PLAN_CACHE",
                          str(ROOT / ".plan_cache" / "plan_cache.json"))
    print(f"device {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"jax {jax.__version__}", flush=True)
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            phase_explicit()
            phase_auto()
            phase_served()
    except SmokeFailure as e:
        print(f"FAIL {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
