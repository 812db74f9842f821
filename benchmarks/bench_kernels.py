"""Kernel-level evidence for the paper's two mechanisms, from compiled
artifacts (CPU host: interpret-mode kernels, compiled XLA around them).

(a) §3.3 — arithmetic intensity rises k× with the unroll-and-jam factor:
    cost_analysis() of the k-step pipelined kernel shows flops/byte scaling
    with k while bytes/sweep stays ~flat (one load + one store per block).

(b) §3.2 — data-reorganization op census: the transpose-layout kernel needs
    exactly 4r assembled-row ops per vector set vs 2r+1 full-width rolls
    per tap for the naive layout (counted analytically per kernel config —
    the Mosaic lane-permute distinction only materializes on real TPU; the
    analytic census is printed alongside the HLO reorg-op count).

(c) ``--smoke`` — resident-vs-roundtrip sweep-engine micro-benchmark: times
    ``ops.stencil_sweep_periodic`` (one layout round-trip per run) against
    ``ops.stencil_run_periodic`` (pad/transpose/crop per sweep) at growing
    step counts and writes the JSON artifact CI uploads
    (``benchmarks/results/bench_kernels_smoke.json``) — the perf
    trajectory record for the layout-resident engine.  The artifact's
    ``ttile_vs_resident`` section compares the time-tiled resident path
    (ttile=4 — one HBM round-trip per ttile·k steps) against the ttile=1
    resident path: measured times, the roofline's modeled HBM-bytes
    ratio, and a bit-identity flag.  On a multi-device
    host (CI forces 8 via ``--xla_force_host_platform_device_count``) the
    artifact gains a ``distributed`` section timing the SHARD-resident
    engine (one transpose per run, halos exchanged in layout) against the
    per-exchange round-trip engine on the same mesh, plus a
    ``minor_axis_vs_axis0`` 2-D-mesh smoke comparing axis-0, minor-axis
    (lane-carry ghost codec) and 2-D-mesh decompositions of one 2-D
    problem.  The ``mxu_vs_pallas`` section compares the banded-matmul
    mxu engine (``core/matrixize.py`` — one dot_general per sweep)
    against the pallas resident engine: modeled roofline-time ratio
    (matmul flops charged at ``peak_flops_mxu``), measured
    interpret-scale ratio, and a PARITY flag — allclose to the f64
    oracle at dtype tolerance, NOT bit-equal (the matmul reassociates
    the tap sum).  ``--mxu`` runs that section alone, writes its own
    artifact, and exits nonzero unless parity holds — the multidevice
    CI gate.

(d) ``--distributed`` — the ``distributed`` section alone, at deeper
    step counts, timing all THREE schedules (roundtrip / serialized
    resident / overlapped resident) with the roofline's modeled
    collective-bytes and end-to-end ratios recorded per row; writes
    ``benchmarks/results/bench_kernels_distributed.json``, appends the
    ratio record to the repo-root ``BENCH_distributed.json`` ledger,
    and exits nonzero unless resident == roundtrip AND overlapped ==
    serialized BITWISE — the multidevice CI gate.  Every row carries
    ``mode: "interpret"`` so dashboards never mistake interpret-scale
    wall-clock for a silicon claim.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layouts, stencils
from repro.kernels import stencil_kernels as sk
from benchmarks.timing import Row, bench

N = 8 * 8 * 64
VL, M = 8, 8


def _intensity(spec, k: int):
    x = jnp.zeros((N,), jnp.float32)
    t = layouts.to_transpose_layout(x, VL, M)
    fn = jax.jit(lambda v: sk.stencil1d_multistep(spec, v, k,
                                                  interpret=True))
    from repro.compat import cost_analysis_dict
    c = cost_analysis_dict(fn.lower(t).compile().cost_analysis())
    flops = float(c.get("flops", 0.0))
    byts = float(c.get("bytes accessed", 1.0))
    return flops, byts, flops / byts


def run(full: bool = False) -> list[Row]:
    rows = []
    spec = stencils.make("1d3p")
    base = None
    for k in [1, 2, 4]:
        flops, byts, ai = _intensity(spec, k)
        if k == 1:
            base = ai
        # sweep-level (whole k-step pass over N points): HBM traffic is one
        # block load + one store per slide regardless of k — the paper's
        # §3.3 claim gives AI exactly ×k; the measured compiled-artifact
        # ratio (per grid step; includes boundary assembles + masked edge
        # updates) is printed alongside.
        ai_sweep = k * spec.flops_per_point / (2 * 4)
        rows.append(Row(
            f"kernel/1d3p/multistep_k{k}", 0.0,
            f"AI_sweep={ai_sweep:.3f} flops/byte (exactly {k}x k=1); "
            f"compiled-artifact flops={flops:.0f} bytes={byts:.0f} "
            f"ratio={ai / base:.2f}x"))

    # analytic reorg-op census per vector set (the §3.2 claim)
    for name in ["1d3p", "1d5p"]:
        s = stencils.make(name)
        ours = 4 * s.r          # 2r assembled rows × (blend + permute)
        naive = (2 * s.r + 1) * M   # one lane-roll per tap per row
        rows.append(Row(
            f"kernel/{name}/reorg_ops_per_VS", 0.0,
            f"transpose_layout={ours}; naive_lane_rolls={naive}; "
            f"reduction={naive / ours:.1f}x"))
    return rows


# ---------------------------------------------------------------------------
# --smoke: resident vs per-sweep-roundtrip sweep engines (CI artifact)
# ---------------------------------------------------------------------------

SMOKE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "results", "bench_kernels_smoke.json")


def _smoke_distributed(steps_list) -> dict:
    """Shard-resident (serialized AND overlapped) vs per-exchange-
    roundtrip distributed engines on the default mesh; skipped (with a
    reason) on single-device hosts.

    Per row: measured times for all three schedules, the measured
    resident-with-overlap vs roundtrip ratio (the acceptance reading),
    the roofline's modeled collective-bytes and modeled end-to-end time
    ratios for the same plans (the exact-strip + overlap economics the
    measured interpret-scale numbers undersell on a CPU host), and two
    parity flags: resident == roundtrip BITWISE and overlapped ==
    serialized BITWISE — the flags CI gates on (``--distributed``)."""
    n_dev = jax.device_count()
    if n_dev < 2:
        return {"skipped": f"needs >=2 devices, have {n_dev}",
                "n_devices": n_dev, "results": [], "parity": True}
    from repro.core.api import StencilPlan
    from repro.distributed import multistep as dms
    from repro.roofline import stencil as rs
    spec = stencils.make("1d3p")
    shape = (n_dev * 4 * 4 * 8,)       # 8 layout blocks per shard
    x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                    jnp.float32)
    kw = dict(k=2, engine="pallas", shards=(n_dev,), vl=4, m=4)
    rt_plan = StencilPlan(scheme="transpose", k=2, vl=4, m=4,
                          backend="distributed", decomp=(n_dev,),
                          sweep="roundtrip")
    ovl_plan = dataclasses.replace(rt_plan, sweep="resident", overlap=True)
    rows = []
    for steps in steps_list:
        rt = bench(lambda: dms.distributed_run(
            spec, x, steps, sweep="roundtrip", **kw),
            warmup=1, iters=3, min_time_s=0.05)
        res = bench(lambda: dms.distributed_run(
            spec, x, steps, sweep="resident", **kw),
            warmup=1, iters=3, min_time_s=0.05)
        ovl = bench(lambda: dms.distributed_run(
            spec, x, steps, sweep="resident", overlap=True, **kw),
            warmup=1, iters=3, min_time_s=0.05)
        a = np.asarray(dms.distributed_run(spec, x, steps,
                                           sweep="roundtrip", **kw))
        b = np.asarray(dms.distributed_run(spec, x, steps,
                                           sweep="resident", **kw))
        c = np.asarray(dms.distributed_run(spec, x, steps,
                                           sweep="resident", overlap=True,
                                           **kw))
        _, _, coll_rt = rs.plan_terms(spec, shape, 4, rt_plan, steps=steps)
        _, _, coll_ov = rs.plan_terms(spec, shape, 4, ovl_plan,
                                      steps=steps)
        t_rt = rs.estimate_plan_time(spec, shape, 4, rt_plan, steps=steps)
        t_ov = rs.estimate_plan_time(spec, shape, 4, ovl_plan, steps=steps)
        row = {"name": f"dist/1d3p/{shape[0]}x{n_dev}dev/steps{steps}",
               "steps": steps, "mode": "interpret",
               "roundtrip_us": rt * 1e6,
               "resident_us": res * 1e6, "overlap_us": ovl * 1e6,
               "speedup": rt / res,
               "overlap_vs_roundtrip": rt / ovl,
               "overlap_vs_serialized": res / ovl,
               "modeled_coll_bytes_ratio": coll_rt / coll_ov,
               "modeled_time_ratio": t_rt / t_ov,
               "resident_eq_roundtrip": bool(np.array_equal(a, b)),
               "overlap_eq_serialized": bool(np.array_equal(b, c))}
        print(f"{row['name']}: shard_roundtrip={rt * 1e6:.0f}us "
              f"shard_resident={res * 1e6:.0f}us "
              f"overlap={ovl * 1e6:.0f}us "
              f"overlap_vs_roundtrip={rt / ovl:.2f}x "
              f"modeled_bytes={coll_rt / coll_ov:.1f}x "
              f"modeled_time={t_rt / t_ov:.2f}x "
              f"parity={row['resident_eq_roundtrip']}"
              f"/{row['overlap_eq_serialized']}")
        rows.append(row)
    # the virtual-halo overhead fix, on record: pallas grid steps per
    # resident k-sweep with the halo-aware kernels vs what the wrapped-
    # periodic variant used to run (2p extra virtual blocks per sweep —
    # the per-sweep compute a tiny nb-blocks shard actually pays)
    blk = kw["vl"] * kw["m"]
    nb_local = shape[0] // n_dev // blk
    gb = sk.sweep_halo_blocks(spec.r, kw["k"], blk)
    grid_info = {"shard_blocks": nb_local,
                 "halo_aware_grid": nb_local + 2 * gb + kw["k"],
                 "virtual_halo_grid": nb_local + 4 * gb + kw["k"]}
    print(f"dist sweep grid: halo-aware={grid_info['halo_aware_grid']} "
          f"(virtual-halo variant ran {grid_info['virtual_halo_grid']})")
    return {"n_devices": n_dev, "shards": [n_dev], "results": rows,
            "parity": all(r["resident_eq_roundtrip"]
                          and r["overlap_eq_serialized"] for r in rows),
            "sweep_grid": grid_info,
            "minor_axis_vs_axis0": _smoke_minor_axis(steps_list, n_dev)}


def _smoke_minor_axis(steps_list, n_dev: int) -> dict:
    """Axis-0 vs minor-axis vs 2-D-mesh decompositions of the SAME 2-D
    problem on the shard-resident engine — the lane-carry ghost codec's
    comparison artifact: same global grid, same (k, vl, m, t0), three
    meshes.  The hard-coded shape only decomposes evenly (incl. the
    t0=2 pipeline tile on the axis-0 mesh) at 4 or 8 devices — CI
    forces 8; other device counts skip with a reason rather than
    crashing the whole smoke artifact."""
    if n_dev not in (4, 8):
        return {"skipped": f"needs a 4- or 8-device host, have {n_dev}",
                "n_devices": n_dev, "results": []}
    from repro.distributed import multistep as dms
    spec = stencils.make("2d5p")
    shape = (16, n_dev * 32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                    jnp.float32)
    meshes = {"axis0": (n_dev, 1), "minor": (1, n_dev),
              "mesh2d": (2, n_dev // 2)}
    kw = dict(k=2, engine="pallas", sweep="resident", vl=4, m=4, t0=2)
    rows = []
    for steps in steps_list:
        row = {"name": f"dist2d/2d5p/{'x'.join(map(str, shape))}"
                       f"/{n_dev}dev/steps{steps}", "steps": steps}
        for label, shards in meshes.items():
            t = bench(lambda s=shards: dms.distributed_run(
                spec, x, steps, shards=s, **kw),
                warmup=1, iters=3, min_time_s=0.05)
            row[f"{label}_us"] = t * 1e6
        row["minor_vs_axis0"] = row["axis0_us"] / row["minor_us"]
        print(f"{row['name']}: axis0={row['axis0_us']:.0f}us "
              f"minor={row['minor_us']:.0f}us "
              f"mesh2d={row['mesh2d_us']:.0f}us "
              f"minor/axis0={row['minor_vs_axis0']:.2f}x")
        rows.append(row)
    return {"n_devices": n_dev, "meshes": meshes, "results": rows}


def _smoke_ttile(steps_list) -> dict:
    """Time-tiled resident engine vs the PR 3 resident path (ttile=1):
    measured times, the roofline's modeled HBM-bytes ratio for the same
    two plans, and a bit-identity flag — the acceptance artifact for the
    temporal-tile axis (>=2x modeled byte cut at steps >= 8·k, results
    bit-identical)."""
    from repro.core.api import StencilPlan
    from repro.kernels import ops
    from repro.roofline import stencil as rs

    cases = [("1d3p", (8 * 8 * 8,), dict(k=2, vl=8, m=8)),
             ("2d5p", (16, 8 * 8 * 2), dict(k=2, vl=8, m=8, t0=4))]
    ttile = 4
    rows = []
    for name, shape, kw in cases:
        spec = stencils.make(name)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                        jnp.float32)
        base = StencilPlan(scheme="transpose", backend="pallas",
                           sweep="resident", **kw)
        for steps in steps_list:
            res = bench(lambda: ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True, **kw),
                warmup=1, iters=3, min_time_s=0.05)
            tt = bench(lambda: ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True, ttile=ttile, **kw),
                warmup=1, iters=3, min_time_s=0.05)
            _, b_base, _ = rs.plan_terms(spec, shape, 4, base, steps=steps)
            _, b_tt, _ = rs.plan_terms(
                spec, shape, 4, dataclasses.replace(base, ttile=ttile),
                steps=steps)
            a = np.asarray(ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True, **kw))
            b = np.asarray(ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True, ttile=ttile, **kw))
            row = {"name": f"{name}/{'x'.join(map(str, shape))}"
                           f"/steps{steps}/ttile{ttile}",
                   "steps": steps, "ttile": ttile,
                   # per-row engine mode: interpret-mode timings must not
                   # be mistaken for compiled-TPU evidence when rows are
                   # aggregated across hosts (the measured-search ttile
                   # preference is mode-dependent)
                   "mode": "interpret",
                   "resident_us": res * 1e6, "ttile_us": tt * 1e6,
                   "speedup": res / tt,
                   "modeled_bytes_ratio": b_base / b_tt,
                   "bit_identical": bool(np.array_equal(a, b))}
            print(f"{row['name']}: resident={res * 1e6:.0f}us "
                  f"ttile={tt * 1e6:.0f}us speedup={res / tt:.2f}x "
                  f"modeled_bytes={b_base / b_tt:.2f}x "
                  f"bit_identical={row['bit_identical']}")
            rows.append(row)
    return {"ttile": ttile, "results": rows}


def _smoke_mxu(steps_list) -> dict:
    """MXU banded-matmul engine vs the pallas resident engine — the
    ``mxu_vs_pallas`` section of the smoke artifact.

    Three readings per case: (a) the roofline's modeled-time ratio for
    the same two plans (``estimate_plan_time`` — mxu matmul flops are
    charged at ``peak_flops_mxu``, so this is the crossover the planner
    actually reasons about), (b) the measured interpret-scale ratio
    (trajectory data — a CPU host timing a jnp-level matmul against an
    interpret-mode pallas loop says nothing about real MXU silicon),
    and (c) a PARITY flag: both engines allclose to the f64 oracle at
    dtype tolerance.  Parity is deliberately NOT bit-identity — the
    banded matmul reassociates the tap sum (see core/matrixize.py) —
    and is the only reading CI gates on (``--mxu``)."""
    from repro.core.api import StencilPlan
    from repro.kernels import ops
    from repro.roofline import stencil as rs

    cases = [("1d3p", (8 * 8 * 8,), dict(k=2, vl=8, m=8)),
             ("2d5p", (16, 8 * 8 * 2), dict(k=2, vl=8, m=8))]
    tol = 1e-4
    rows = []
    for name, shape, kw in cases:
        spec = stencils.make(name)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                        jnp.float32)
        pal_plan = StencilPlan(scheme="transpose", backend="pallas",
                               sweep="resident",
                               t0=None if spec.ndim == 1 else shape[0] // 4,
                               **kw)
        mxu_plan = StencilPlan(scheme="transpose", backend="mxu", **kw)
        for steps in steps_list:
            pal = bench(lambda: ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True,
                t0=pal_plan.t0, **kw), warmup=1, iters=3, min_time_s=0.05)
            mxu = bench(lambda: ops.stencil_sweep_mxu(
                spec, x, steps, **kw), warmup=1, iters=3, min_time_s=0.05)
            t_pal = rs.estimate_plan_time(spec, shape, 4, pal_plan,
                                          steps=steps)
            t_mxu = rs.estimate_plan_time(spec, shape, 4, mxu_plan,
                                          steps=steps)
            want = np.asarray(x, np.float64)
            for _ in range(steps):
                want = stencils.numpy_apply_once(spec, want)
            a = np.asarray(ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True, t0=pal_plan.t0, **kw))
            b = np.asarray(ops.stencil_sweep_mxu(spec, x, steps, **kw))
            parity = bool(
                np.allclose(b, want.astype(np.float32), rtol=tol, atol=tol)
                and np.allclose(b, a, rtol=tol, atol=tol))
            row = {"name": f"mxu/{name}/{'x'.join(map(str, shape))}"
                           f"/steps{steps}",
                   "steps": steps, "pallas_us": pal * 1e6,
                   "mxu_us": mxu * 1e6,
                   "measured_mxu_vs_pallas": mxu / pal,
                   "modeled_mxu_vs_pallas": t_mxu / t_pal,
                   "parity": parity}
            print(f"{row['name']}: pallas={pal * 1e6:.0f}us "
                  f"mxu={mxu * 1e6:.0f}us "
                  f"measured={mxu / pal:.2f}x "
                  f"modeled={t_mxu / t_pal:.2f}x parity={parity}")
            rows.append(row)
    return {"tolerance": tol, "results": rows,
            "parity": all(r["parity"] for r in rows)}


SERVING_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "results", "bench_kernels_serving.json")

DISTRIBUTED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "results",
                                "bench_kernels_distributed.json")

# repo-root running ledger of distributed ratios: every --smoke /
# --distributed run APPENDS one record, so the perf trajectory across
# commits is greppable without unpacking CI artifacts
BENCH_DISTRIBUTED_LEDGER = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_distributed.json")


def _append_distributed_ledger(dist: dict) -> None:
    """Append this run's resident-vs-roundtrip and overlap-vs-serialized
    ratios to the repo-root ``BENCH_distributed.json`` ledger."""
    rows = dist.get("results") or []
    if not rows:
        return
    record = {
        "backend": jax.default_backend(),
        "n_devices": dist.get("n_devices"),
        "mode": rows[0].get("mode", "interpret"),
        "parity": dist.get("parity"),
        "resident_vs_roundtrip": [
            {"steps": r["steps"], "ratio": r["speedup"]} for r in rows],
        "overlap_vs_roundtrip": [
            {"steps": r["steps"], "ratio": r["overlap_vs_roundtrip"],
             "modeled_coll_bytes_ratio": r["modeled_coll_bytes_ratio"],
             "modeled_time_ratio": r["modeled_time_ratio"]}
            for r in rows],
        "overlap_vs_serialized": [
            {"steps": r["steps"], "ratio": r["overlap_vs_serialized"]}
            for r in rows],
    }
    ledger = []
    if os.path.exists(BENCH_DISTRIBUTED_LEDGER):
        try:
            with open(BENCH_DISTRIBUTED_LEDGER) as f:
                ledger = json.load(f)
        except (OSError, ValueError):
            ledger = []
    if not isinstance(ledger, list):
        ledger = [ledger]
    ledger.append(record)
    with open(BENCH_DISTRIBUTED_LEDGER, "w") as f:
        json.dump(ledger, f, indent=1)
    print(f"appended distributed ratios to {BENCH_DISTRIBUTED_LEDGER}")


def distributed(out_path: str | None = None) -> dict:
    """``--distributed``: the distributed section alone, written to its
    own JSON artifact and appended to the repo-root ledger.  Exit status
    gates on PARITY only (resident == roundtrip bitwise AND overlapped
    == serialized bitwise); throughput ratios are recorded, not gated —
    interpret-scale kernel time dominates a CPU host, so the modeled
    collective-bytes / modeled-time ratios carry the claim."""
    # deeper runs than --smoke: the roundtrip engine pays its per-
    # exchange transpose/untranspose round-trips linearly in steps, so
    # the measured overlap-vs-roundtrip ratio needs depth to show even
    # at interpret scale (the modeled ratios carry it at any depth)
    payload = {"bench": "distributed_resident_overlap",
               "backend": jax.default_backend(),
               "n_devices": jax.device_count(),
               "distributed": _smoke_distributed((8, 16, 32, 64))}
    out_path = out_path or DISTRIBUTED_PATH
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out_path}")
    _append_distributed_ledger(payload["distributed"])
    return payload

MXU_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "bench_kernels_mxu.json")


def mxu(out_path: str | None = None) -> dict:
    """``--mxu``: the mxu_vs_pallas section alone, written to its own
    JSON artifact.  Exit status gates on PARITY only (both engines must
    match the f64 oracle — and each other — at dtype tolerance);
    modeled and measured ratios are recorded, not gated."""
    payload = {"bench": "mxu_vs_pallas",
               "backend": jax.default_backend(),
               "n_devices": jax.device_count(),
               "mxu_vs_pallas": _smoke_mxu((8, 16, 32))}
    out_path = out_path or MXU_PATH
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out_path}")
    return payload


def _smoke_serving(n_req: int = 64, steps: int = 8,
                   shape=(4096,)) -> dict:
    """Continuous-batched serving vs the one-at-a-time sweep loop — the
    ``serving`` section of the smoke artifact.

    Drives ``n_req`` same-signature requests (4 simulated tenants)
    through (a) the legacy synchronous ``StencilService.sweep`` loop and
    (b) ``sweep_async``'s StencilSweepBatcher, after warming both paths,
    and reports sustained sweeps/sec, per-request p50/p99 latency on the
    batched path, and a bit-identity flag (batched results vs the
    sequential loop, bitwise).  Each path runs ``rounds`` timed rounds
    and reports its best (same hygiene as :func:`benchmarks.timing.\
bench` — one noisy round on a shared CI host shouldn't decide the
    trajectory).  Throughput is trajectory data (non-gating);
    bit-identity is the CI gate (``--serving``)."""
    import tempfile
    import time

    from repro.serve.batcher import StencilSweepBatcher
    from repro.serve.engine import StencilService

    name = "1d3p"
    rounds = 3
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
          for _ in range(n_req)]
    with tempfile.TemporaryDirectory() as td:
        with StencilService(cache_path=os.path.join(td, "p.json")) as svc:
            # --- one-at-a-time loop (the pre-batcher serving path) ----
            jax.block_until_ready(svc.sweep(name, xs[0], steps))  # warm
            seq_s = float("inf")
            for _ in range(rounds):
                t0 = time.perf_counter()
                seq = [jax.block_until_ready(svc.sweep(name, x, steps))
                       for x in xs]
                seq_s = min(seq_s, time.perf_counter() - t0)

            # --- continuous-batched path ------------------------------
            batcher = StencilSweepBatcher(svc, max_queue=2 * n_req)
            warm = [batcher.submit(name, x, steps, tenant="warm")
                    for x in xs[:batcher.max_slots]]      # slot warmup
            for f in warm:
                f.result(timeout=120)
            bat_s, lat = float("inf"), []
            for _ in range(rounds):
                r_lat: list[float] = []
                t0 = time.perf_counter()
                futs = []
                for i, x in enumerate(xs):
                    t_sub = time.perf_counter()
                    f = batcher.submit(name, x, steps,
                                       tenant=f"t{i % 4}")
                    f.add_done_callback(
                        lambda f, t=t_sub: r_lat.append(
                            time.perf_counter() - t))
                    futs.append(f)
                got = [f.result(timeout=120) for f in futs]
                r_s = time.perf_counter() - t0
                if r_s < bat_s:
                    bat_s, lat = r_s, r_lat
            stats = batcher.stats
            batcher.close()

    bit_identical = all(np.array_equal(np.asarray(a), np.asarray(b))
                        for a, b in zip(seq, got))
    lat = sorted(lat)
    row = {
        "name": f"serving/{name}/{'x'.join(map(str, shape))}"
                f"/steps{steps}/n{n_req}",
        "n_requests": n_req, "steps": steps,
        "n_devices": jax.device_count(),
        "sequential_s": seq_s, "batched_s": bat_s,
        "sequential_sweeps_per_s": n_req / seq_s,
        "batched_sweeps_per_s": n_req / bat_s,
        "speedup": seq_s / bat_s,
        "p50_ms": 1e3 * lat[len(lat) // 2],
        "p99_ms": 1e3 * lat[min(len(lat) - 1,
                                int(len(lat) * 0.99))],
        "batches": stats["batches"], "programs": stats["programs"],
        "bit_identical": bit_identical,
    }
    print(f"{row['name']}: sequential={row['sequential_sweeps_per_s']:.0f}"
          f"/s batched={row['batched_sweeps_per_s']:.0f}/s "
          f"speedup={row['speedup']:.2f}x p50={row['p50_ms']:.1f}ms "
          f"p99={row['p99_ms']:.1f}ms batches={row['batches']} "
          f"bit_identical={bit_identical}")
    from repro.serve.batcher import SLOT_COUNTS
    return {"results": [row], "bit_identical": bit_identical,
            "slot_counts": list(SLOT_COUNTS)}


def serving(out_path: str | None = None) -> dict:
    """``--serving``: the serving section alone, written to its own JSON
    artifact.  Exit status gates on BIT-IDENTITY only (batched results
    must equal the sequential loop bitwise); throughput numbers are
    recorded, not gated."""
    payload = {"bench": "continuous_batched_serving",
               "backend": jax.default_backend(),
               "n_devices": jax.device_count(),
               "serving": _smoke_serving()}
    out_path = out_path or SERVING_PATH
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out_path}")
    return payload


def smoke(steps_list=(8, 16, 32), out_path: str | None = None) -> dict:
    """Micro-benchmark the layout-resident sweep engine against the
    per-sweep pad/transpose/crop path, at CPU-interpret-friendly scale,
    and write the JSON artifact.  The resident win grows with ``steps``
    (the round-trip amortizes over the run)."""
    from repro.kernels import ops

    cases = [("1d3p", (8 * 8 * 8,), dict(k=2, vl=8, m=8)),
             ("2d5p", (16, 8 * 8 * 2), dict(k=2, vl=8, m=8, t0=4))]
    results = []
    for name, shape, kw in cases:
        spec = stencils.make(name)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(shape),
                        jnp.float32)
        for steps in steps_list:
            rt = bench(lambda: ops.stencil_run_periodic(
                spec, x, steps, interpret=True, **kw),
                warmup=1, iters=3, min_time_s=0.05)
            res = bench(lambda: ops.stencil_sweep_periodic(
                spec, x, steps, interpret=True, **kw),
                warmup=1, iters=3, min_time_s=0.05)
            row = {"name": f"{name}/{'x'.join(map(str, shape))}/steps{steps}",
                   "steps": steps, "roundtrip_us": rt * 1e6,
                   "resident_us": res * 1e6, "speedup": rt / res}
            print(f"{row['name']}: roundtrip={rt * 1e6:.0f}us "
                  f"resident={res * 1e6:.0f}us speedup={rt / res:.2f}x")
            results.append(row)
    payload = {"bench": "resident_vs_roundtrip_sweep",
               "backend": jax.default_backend(),
               "device": jax.devices()[0].device_kind,
               # both timed paths pin interpret=True above — comparable
               # CPU-interpret-scale numbers on every host, incl. TPU
               "mode": "interpret",
               "results": results,
               "ttile_vs_resident": _smoke_ttile(steps_list),
               "mxu_vs_pallas": _smoke_mxu(steps_list),
               # + a steps=64 row: the overlap-vs-roundtrip acceptance
               # reading needs depth (the roundtrip engine pays its
               # per-exchange re-layout linearly in steps)
               "distributed": _smoke_distributed(tuple(steps_list) + (64,)),
               "serving": _smoke_serving()}
    out_path = out_path or SMOKE_PATH
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out_path}")
    _append_distributed_ledger(payload["distributed"])
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="resident-vs-roundtrip sweep engine bench → JSON")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--serving", action="store_true",
                    help="continuous-batched serving bench → JSON; exits "
                         "nonzero if batched != sequential bitwise")
    ap.add_argument("--mxu", action="store_true",
                    help="mxu-vs-pallas bench → JSON; exits nonzero "
                         "unless both engines match the f64 oracle at "
                         "dtype tolerance")
    ap.add_argument("--distributed", action="store_true",
                    help="distributed resident/overlap bench → JSON; "
                         "exits nonzero unless resident == roundtrip "
                         "and overlapped == serialized bitwise")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    if args.distributed:
        payload = distributed()
        if not payload["distributed"]["parity"]:
            raise SystemExit(
                "distributed parity FAILED: resident != roundtrip or "
                "overlapped != serialized schedule (bitwise)")
        return
    if args.serving:
        payload = serving()
        if not payload["serving"]["bit_identical"]:
            raise SystemExit(
                "serving bit-identity FAILED: batched results differ "
                "from the sequential sweep loop")
        return
    if args.mxu:
        payload = mxu()
        if not payload["mxu_vs_pallas"]["parity"]:
            raise SystemExit(
                "mxu parity FAILED: banded-matmul engine differs from "
                "the f64 oracle / pallas resident engine beyond dtype "
                "tolerance")
        return
    if args.smoke:
        smoke()
        return
    for row in run(full=args.full):
        print(row)


if __name__ == "__main__":
    main()
