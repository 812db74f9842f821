#!/usr/bin/env python3
"""Chip benchmark of ``StencilProblem.run``: one cell, one run.

    python3 bench/run.py --workload 2d5p.long --seed 7 --seconds 20 --trace 0

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a
configuration (grid, dtype, stencil taps) under a traffic mix.  Every mix
is a closed loop with one caller: the field ``x`` is made on the device
from ``--seed``, and each call ``x = run(x, steps_per_call)`` is awaited
before the next is issued, as a time-stepping code that takes a snapshot
every ``steps_per_call`` steps does.

Set-up (timed from process start) resolves the plan, compiles, makes the
input and makes one warm call.  The window then runs calls for
``--seconds`` seconds.  With ``--trace 0`` the result line holds the
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the result line holds the per-layer metrics, read from the trace by
``metrics/<name>.py``.

After the window, a sample of the window's calls, drawn from the seed,
is checked against the plain reference in ``work.py``: each number
compared is printed with its limit on the last lines of standard error
and under ``checks`` in the result line, which is the last line of
standard output.  Without a TPU, with another chip count than the
cell's, or with a configuration whose ``mesh`` does not make that count,
the run prints no result and exits 2.

A cell's first run in a checkout tunes the plan (``plan="auto"`` finds no
entry in the plan cache); it says so on standard error and in the result
line's ``plan_cache`` ("miss"), so that its ``setup_s`` is recorded apart.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the TPU runtime would log to a fixed /tmp path, shared between checkouts
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import cells  # noqa: E402

TRACE_DIR = ".bench_trace"       # under the checkout, emptied per run
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def info(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def p95(values) -> float:
    """Nearest-rank 95th percentile."""
    s = sorted(values)
    return s[max(math.ceil(0.95 * len(s)) - 1, 0)]


def median(values) -> float:
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2


def use_caches(root: Path) -> None:
    """The plan cache and JAX's compilation cache at fixed paths in the
    checkout (JAX_COMPILATION_CACHE_DIR, where set, wins)."""
    import jax
    os.environ["REPRO_PLAN_CACHE"] = str(root / ".plan_cache"
                                         / "plan_cache.json")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def make_input(shape, dtype, seed: int, mesh=None):
    """The field, made on the device in one jitted call from ``seed``
    (any whole number: the low 32 bits key it, the rest is folded in).
    With ``mesh`` (``work.field_mesh``) it is made in shards, laid out by
    ``work.field_sharding``: no device holds the whole grid, and the
    values are those of the one-device field."""
    import jax
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    layout = {}
    if mesh is not None:
        from bench import work
        layout["out_shardings"] = work.field_sharding(mesh)
    return jax.jit(lambda k: jax.random.normal(k, tuple(shape), dtype),
                   **layout)(key)


def cell_mesh(cell: cells.Cell):
    """The harness's mesh for a config with a ``mesh`` key, else None."""
    if "mesh" not in cell.config:
        return None
    from bench import work
    return work.field_mesh(cell.config["mesh"])


class Setup(types.SimpleNamespace):
    """What set-up hands the window: ``call`` (the timed path), ``plan()``
    (a description of the plan, for the log) and ``plan_cache`` ("hit" or
    "miss" before the first call, None where the path has no plan)."""


def program_path(cell: cells.Cell) -> Setup:
    """The system under test: ``StencilProblem.run`` as the traffic mix
    drives it, with the default ``plan="auto"`` on every call."""
    import jax.numpy as jnp
    from repro.core import autotune
    from repro.core.api import StencilProblem
    cfg, tr = cell.config, cell.traffic
    if tr["planner"] != "auto":
        raise ValueError(f"unknown planner {tr['planner']!r}")
    steps = int(tr["steps_per_call"])
    problem = StencilProblem(cfg["stencil"], tuple(cfg["shape"]),
                             jnp.dtype(cfg["dtype"]))
    # the key that run(x, steps) looks up, before the first call fills it
    cached = autotune.cached_plan(problem, steps=steps,
                                  generic_fallback=False)

    def call(x):
        return problem.run(x, steps)

    def plan():
        return autotune.best_plan(problem, steps=steps)
    return Setup(call=call, plan=plan,
                 plan_cache="miss" if cached is None else "hit")


def window(call, x, seconds: float, n_samples: int, rng: random.Random):
    """Closed loop: issue, await, carry ``x`` over, until ``seconds``
    have passed.  Keeps a reservoir of ``n_samples`` (input, output)
    pairs drawn with ``rng``.  Returns the per-call host times, the
    window's length, the samples and the final state."""
    import jax
    from jax.profiler import TraceAnnotation
    calls, samples = [], []
    with TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        due = t_start
        while True:
            with TraceAnnotation("bench.call"):
                with TraceAnnotation("bench.dispatch"):
                    y = call(x)
                ret = time.perf_counter()
                with TraceAnnotation("bench.wait"):
                    jax.block_until_ready(y)
                done = time.perf_counter()
            calls.append((due, ret, done))
            i = len(calls) - 1
            if len(samples) < n_samples:
                samples.append((i, x, y))
            else:
                j = rng.randrange(i + 1)
                if j < n_samples:
                    samples[j] = (i, x, y)
            x, due = y, done
            if done - t_start >= seconds:
                break
    return calls, done - t_start, samples, x


def check(samples, taps, steps: int, limit: float, mesh=None):
    """Each sampled call's output against the plain reference applied to
    the same input; the largest relative error is compared.  With
    ``mesh`` both are first laid out on it, whatever layout the program
    chose, and compared shard by shard."""
    import jax
    from bench import work
    errs = []
    for i, xin, yout in samples:
        if mesh is None:
            ref = work.reference_steps(xin, taps, steps)
        else:
            sharding = work.field_sharding(mesh)
            xin = jax.device_put(xin, sharding)
            yout = jax.device_put(yout, sharding)
            ref = work.mesh_reference_steps(xin, taps, steps, mesh)
        errs.append(float(work.rel_err(yout, ref)))
        del ref
    worst = max(errs) if errs else math.inf
    failed = sum(1 for e in errs if not (e <= limit))
    return worst, failed, errs


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def read_per_layer(cell: cells.Cell, ctx, bench_dir: Path) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cells.metric_reader(m["name"], bench_dir).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, root: Path = ROOT, bench_dir: Path = BENCH,
         require_chip: bool = True, path=None) -> int:
    """One run.  Tests pass ``require_chip=False`` to drive a run on the
    CPU, and ``path`` (a function of the cell returning a ``Setup``) to put
    another timed path in the program's place."""
    args = parse(argv)
    cell = cells.load_cell(root, args.workload, bench_dir)
    shards = cell.config.get("mesh", [1] * len(cell.config["shape"]))
    if (len(shards) != len(cell.config["shape"])
            or math.prod(shards) != cell.chips):
        info(f"bench: {args.workload} needs {cell.chips} chip(s), but its "
             f"config lays the {len(cell.config['shape'])}-D field out as "
             f"mesh {cell.config.get('mesh')}")
        return 2
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            info(f"bench: no TPU (platform {devs[0].platform!r}); "
                 "refusing to run")
            return 2
        if len(devs) != cell.chips:
            info(f"bench: {args.workload} needs {cell.chips} chip(s), "
                 f"JAX sees {len(devs)}")
            return 2
    try:
        import repro.core.api  # noqa: F401
    except ImportError as e:
        info(f"bench: the program under test is missing ({e})")
        return 2
    peaks = cells.load_json(bench_dir / "peaks.json")
    kind = devs[0].device_kind
    if require_chip and kind not in peaks:
        info(f"bench: device kind {kind!r} not in peaks.json")
        return 2
    use_caches(root)

    from bench import work
    cfg, tr = cell.config, cell.traffic
    taps = work.taps_from_config(cfg)
    steps = int(tr["steps_per_call"])
    shape = tuple(cfg["shape"])
    mesh = cell_mesh(cell)
    setup = (path or program_path)(cell)
    plan_cache = getattr(setup, "plan_cache", None)
    if plan_cache == "miss":
        info("bench: plan cache miss: this run tunes the plan, and its "
             "setup_s is a tuning run's")
    x = make_input(shape, cfg["dtype"], args.seed, mesh)
    x = jax.block_until_ready(setup.call(x))      # warm: tunes, compiles
    info(f"bench: plan {setup.plan()} (plan cache {plan_cache})")
    vpu = None
    if args.trace:
        from bench import vpu_peak
        vpu = vpu_peak.measure(cfg["dtype"])
        info(f"bench: vpu_peak {vpu:.6e} ops/s ({cfg['dtype']})")
    setup_s = time.perf_counter() - T_PROCESS

    compiles = []

    def on_event(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    rng = random.Random(args.seed)
    n_samples = int(tr["check_calls"])
    itemsize = jax.numpy.dtype(cfg["dtype"]).itemsize
    grid_bytes = work.points(shape) * itemsize
    info(f"bench: memory peak before the window {memory_peak(devs)} bytes "
         f"(set-up and the warm call); the check's samples hold up to "
         f"{2 * n_samples * grid_bytes // cell.chips} bytes more a chip in "
         "the window")
    trace_dir = root / TRACE_DIR / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        calls, window_s, samples, x = window(setup.call, x, args.seconds,
                                             n_samples, rng)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    in_window = len(compiles)
    mem_peak = memory_peak(devs)
    del x
    n = len(calls)
    ms = sorted((d - due) * 1e3 for due, _, d in calls)
    mid = median(ms)
    info(f"bench: {n} calls in {window_s:.6f} s; call ms median {mid:.4f} "
         f"p95 {p95(ms):.4f} max {ms[-1]:.4f}, "
         f"{sum(v > 2 * mid for v in ms)} over twice the median; "
         f"compilations in the window: {in_window}")

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": None, "attempted": n, "failed": None}
    if args.trace:
        from bench import trace
        reduced = trace.reduce(trace.find_xplane(str(trace_dir)))
        ctx = types.SimpleNamespace(
            trace=reduced, cell=cell, calls=calls, n_calls=n,
            window_s=window_s, chips=cell.chips, steps=steps,
            points=work.points(shape), vpu_ops_per_s=vpu,
            peak=peaks.get(kind, {}),
            ops_per_call=work.least_ops_per_call(taps, shape, steps),
            bytes_per_call=work.least_bytes_per_call(shape, itemsize),
            note=info)
        metrics = read_per_layer(cell, ctx, bench_dir)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        breakdown = reduced.breakdown()
    else:
        points_steps = work.points(shape) * steps * n
        metrics = {
            "gstencil_s": {"value": points_steps / window_s / 1e9,
                           "unit": "Gstencil/s"},
            "call_ms_p95": {"value": p95(
                (d - due) * 1e3 for due, _, d in calls), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        breakdown = None

    limit = float(cell.limits["rel_err"]["limit"])
    t_check = time.perf_counter()
    worst, failed, errs = check(samples, taps, steps, limit, mesh)
    info(f"bench: the check of {len(errs)} samples took "
         f"{time.perf_counter() - t_check:.3f} s")
    del samples
    correct = math.isfinite(worst) and worst <= limit
    result.update(correct=correct, failed=failed, metrics=metrics,
                  device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["plan_cache"] = plan_cache
    result["checks"] = {"rel_err": {"value": worst, "limit": limit}}
    for i, e in enumerate(errs):
        info(f"check sample {i}: rel_err {e!r}")
    info(f"check rel_err {worst!r} limit {limit!r} "
         f"{'ok' if correct else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
