"""The harness on a four-device mesh, run by ``test_mesh.py`` in a process
of its own with four forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/_mesh_cases.py <scratch dir>

Prints one JSON line per case, ``{"case": ..., "ok": ..., "detail": ...}``.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import traceback
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from bench import limits, run, work  # noqa: E402
from bench.tests import helpers  # noqa: E402

LIMIT = json.loads((helpers.BENCH / "limits" / "2d5p.long.json")
                   .read_text())["rel_err"]["limit"]
SEED = 2**33 + 12345
SHAPE4, STEPS4 = (256, 512), 8          # the test-only four-chip cell
ARGS = ["--workload", "t4.long", "--seed", str(SEED), "--seconds", "0.5",
        "--trace", "0"]
CASES = {}


def case(name):
    def put(fn):
        CASES[name] = fn
        return fn
    return put


def taps_of(name):
    return work.taps_from_config(
        {"taps": {"2d5p": helpers.TAPS_2D5P, "1d3p": helpers.TAPS_1D3P}[name]})


def field(shape, seed=5):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def reference_case(stencil, shape, shards, steps):
    def body():
        x = field(shape)
        mesh = work.field_mesh(shards)
        want = work.reference_steps(x, taps_of(stencil), steps)
        got = work.mesh_reference_steps(
            jax.device_put(x, work.field_sharding(mesh)), taps_of(stencil),
            steps, mesh)
        assert got.sharding == work.field_sharding(mesh), got.sharding
        same = bool(jnp.array_equal(jax.device_get(got),
                                    jax.device_get(want)))
        return same, f"bit-identical {same}"
    return body


for _shards in ((2, 2), (4, 1), (1, 4)):
    for _steps in (8, 128):
        case(f"reference-2d5p-{'x'.join(map(str, _shards))}-{_steps}")(
            reference_case("2d5p", (512, 512), _shards, _steps))
for _steps in (8, 128):
    case(f"reference-1d3p-4-{_steps}")(
        reference_case("1d3p", (1 << 16,), (4,), _steps))


@case("reference-refuses-a-halo-wider-than-a-shard")
def _():
    mesh = work.field_mesh((4, 1))
    x = jax.device_put(field((64, 64)), work.field_sharding(mesh))
    try:
        work.mesh_reference_steps(x, taps_of("2d5p"), 17, mesh)
    except ValueError as e:
        return "wider than the shard extent 16 on axis 0" in str(e), str(e)
    return False, "no error"


@case("check-takes-an-explicit-axes-output")
def _():
    """The program's meshes have Explicit axes; the check lays such an
    output onto its own mesh before it compares."""
    taps, steps = taps_of("2d5p"), 8
    mesh = work.field_mesh((2, 2))
    x = jax.device_put(field((128, 256)), work.field_sharding(mesh))
    y = work.mesh_reference_steps(x, taps, steps, mesh)
    explicit = jax.make_mesh((4, 1), ("p", "q"),
                             axis_types=(jax.sharding.AxisType.Explicit,) * 2)
    ys = jax.device_put(y, NamedSharding(explicit, PartitionSpec("p", "q")))
    worst, failed, _ = run.check([(0, x, ys)], taps, steps, LIMIT, mesh)
    return worst == 0.0 and failed == 0, f"rel_err {worst!r}"


@case("reference-never-gathers")
def _():
    """Compiled for a 4096^2 field on (2, 2), 128 steps: no collective
    but the ring's permutes, and each device's temporaries no more than
    the one-device reference needs for a field of one shard's size.
    (The CPU backend keeps four slices of the slab apart in each step,
    so that is five ghost-extended shards here.)"""
    n, steps, taps = 4096, 128, taps_of("2d5p")
    mesh = work.field_mesh((2, 2))
    whole = jax.ShapeDtypeStruct((n, n), jnp.float32,
                                 sharding=work.field_sharding(mesh))
    shard = jax.ShapeDtypeStruct((n // 2, n // 2), jnp.float32)
    comp = work.mesh_reference_steps.lower(whole, taps, steps, mesh).compile()
    one = work.reference_steps.lower(shard, taps, steps).compile()
    err = work.rel_err.lower(whole, whole).compile()
    text = comp.as_text() + err.as_text()
    bad = [c for c in ("all-gather", "all-to-all") if c in text]
    temp = comp.memory_analysis().temp_size_in_bytes
    one_temp = one.memory_analysis().temp_size_in_bytes
    slab = (n // 2 + 2 * steps) ** 2 * 4
    ok = not bad and "collective-permute" in text and temp <= 1.01 * one_temp
    return ok, (f"collectives {bad}; temp {temp} bytes = "
                f"{temp / slab:.3f} ghost-extended shards; one-device "
                f"reference on one shard {one_temp}")


@case("mesh-input-is-the-one-device-field")
def _():
    out = []
    for shape, shards in (((512, 256), (2, 2)), ((512, 256), (4, 1)),
                          ((1 << 12,), (4,))):
        mesh = work.field_mesh(shards)
        a = run.make_input(shape, "float32", SEED, mesh)
        b = run.make_input(shape, "float32", SEED)
        same = bool(jnp.array_equal(jax.device_get(a), jax.device_get(b)))
        parts = {tuple(s.data.shape) for s in a.addressable_shards}
        want = {tuple(n // s for n, s in zip(shape, shards))}
        out.append(same and a.sharding == work.field_sharding(mesh)
                   and parts == want and len(a.addressable_shards) == 4)
    return all(out), f"{out}"


def tree(tmp: Path) -> Path:
    return helpers.tree(
        tmp, {"t4": ("2d5p", helpers.TAPS_2D5P, SHAPE4, (2, 2))},
        {"t4.long": ("t4", STEPS4, "auto", 4)}, limit=LIMIT)


def sharded_path(cell):
    """The program on its distributed halo path, on a (2, 2) mesh."""
    from repro.core.api import StencilPlan, StencilProblem
    problem = StencilProblem(cell.config["stencil"],
                             tuple(cell.config["shape"]))
    plan = StencilPlan(scheme="transpose", k=4, backend="distributed",
                       sweep="resident", decomp=(2, 2), m=None)
    steps = int(cell.traffic["steps_per_call"])
    return run.Setup(call=lambda x: problem.run(x, steps, plan),
                     plan=lambda: str(plan), plan_cache=None)


def wrapped(fault):
    def path(cell):
        prog = sharded_path(cell)
        return run.Setup(call=lambda x: fault(prog.call(x), x),
                         plan=prog.plan, plan_cache=None)
    return path


def on_harness_mesh(y):
    return jax.device_put(y, work.field_sharding(work.field_mesh((2, 2))))


def no_exchange(cell):
    """Each shard stepped periodically on itself: the halo exchange left
    out."""
    mesh = work.field_mesh(cell.config["mesh"])
    spec = PartitionSpec(*mesh.axis_names)
    taps = work.taps_from_config(cell.config)
    steps = int(cell.traffic["steps_per_call"])
    step = jax.jit(jax.shard_map(
        lambda v: work.reference_steps(v, taps, steps), mesh=mesh,
        in_specs=spec, out_specs=spec))
    return run.Setup(call=lambda x: step(on_harness_mesh(x)),
                     plan=lambda: "no exchange", plan_cache=None)


PATHS = {
    "sharded-program-is-correct": (sharded_path, True),
    "no-halo-exchange-is-not-correct": (no_exchange, False),
    "state-returned-unchanged-is-not-correct": (
        wrapped(lambda y, x: x), False),
    "non-finite-answer-is-not-correct": (
        wrapped(lambda y, x: on_harness_mesh(y).at[0, 0].set(jnp.nan)),
        False),
    "answer-altered-where-produced-is-not-correct": (
        wrapped(lambda y, x: on_harness_mesh(y).at[3, 5].add(0.01)),
        False),
    "bfloat16-control-is-not-correct": (
        lambda cell: limits.control_path(cell, sharded_path(cell)), False),
}


def run_case(t: Path, path, want: bool):
    """One run of the test-only four-chip cell with ``path`` in the
    program's place; ``correct`` has to read ``want``."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(ARGS, root=t, bench_dir=t, require_chip=False,
                      path=path)
    res = helpers.last_json(out.getvalue())
    ok = (rc == 0 and res["correct"] is want
          and res["device"]["count"] == 4 and res["attempted"] >= 1)
    return ok, json.dumps({k: res[k] for k in ("correct", "failed",
                                                "attempted", "checks")})


def main(scratch: str) -> int:
    assert jax.device_count() == 4, jax.devices()
    t = tree(Path(scratch))
    todo = [(name, fn) for name, fn in CASES.items()] + [
        (name, lambda p=path, w=want: run_case(t, p, w))
        for name, (path, want) in PATHS.items()]
    for name, fn in todo:
        try:
            ok, detail = fn()
        except Exception:      # one case's failure must not hide the rest
            ok, detail = False, traceback.format_exc()
        print(json.dumps({"case": name, "ok": bool(ok), "detail": detail}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
