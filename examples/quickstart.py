"""Quickstart: the paper's vectorization scheme on a 1-D stencil.

    PYTHONPATH=src python examples/quickstart.py

Runs the same 1D3P problem through every vectorization scheme (multiload /
reorg / DLT / transpose layout), the k-step unroll-and-jam, the tessellate
tiler and the Pallas kernel, checks they all agree with the oracle, prints
a mini benchmark, and finishes with ``plan="auto"`` — the measured-search
autotuner picking (and caching) the fastest plan for this machine."""
import os
import sys
import tempfile
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import stencils, tessellate, vectorize
from repro.core.api import StencilPlan, StencilProblem
from repro.kernels import ops, ref

N, STEPS = 1 << 20, 8


def main():
    compile_cache.enable()
    prob = StencilProblem("1d3p", (N,))
    x = prob.init(seed=0)
    oracle = prob.reference(x, STEPS)

    plans = {
        "multiload": StencilPlan(scheme="multiload", k=1),
        "reorg": StencilPlan(scheme="reorg", k=1),
        "dlt": StencilPlan(scheme="dlt", k=1, vl=8),
        "transpose (ours)": StencilPlan(scheme="transpose", k=1, vl=8),
        "ours + 2-step": StencilPlan(scheme="transpose", k=2),
        "tessellate(H=4)": StencilPlan(scheme="fused", k=1,
                                       tiling="tessellate", tile=(4096,),
                                       height=4),
    }
    print(f"1D3P, N={N}, {STEPS} steps — all schemes vs oracle")
    for name, plan in plans.items():
        t0 = time.perf_counter()
        y = prob.run(x, STEPS, plan)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(y - oracle)))
        gf = prob.model_flops(STEPS) / dt / 1e9
        print(f"  {name:18s} max_err={err:.2e}  {dt*1e3:7.1f} ms "
              f"({gf:5.2f} GFlop/s, first call incl. compile)")
        assert err < 1e-3, name

    # Pallas kernel path (dirichlet BC — its own oracle)
    spec = stencils.make("1d3p")
    y = ops.stencil_run(spec, x, steps=STEPS, k=2, vl=8, m=8,
                        interpret=True)
    want = ref.multistep_ref(spec, x, STEPS)
    err = float(jnp.max(jnp.abs(y - want)))
    print(f"  {'pallas kernel k=2':18s} max_err={err:.2e}  "
          f"(interpret mode on CPU)")
    assert err < 1e-3

    # plan="auto": measured search over every legal plan, winner cached
    if "REPRO_PLAN_CACHE" not in os.environ:
        os.environ["REPRO_PLAN_CACHE"] = os.path.join(tempfile.mkdtemp(),
                                                      "plans.json")
    t0 = time.perf_counter()
    y = prob.run(x, STEPS, plan="auto")       # tunes (first call, measured)
    jax.block_until_ready(y)
    t_tune = time.perf_counter() - t0
    t0 = time.perf_counter()
    y = prob.run(x, STEPS, plan="auto")       # cache hit — no measurement
    jax.block_until_ready(y)
    dt = time.perf_counter() - t0
    err = float(jnp.max(jnp.abs(y - oracle)))
    print(f"  {'plan=auto':18s} max_err={err:.2e}  {dt*1e3:7.1f} ms "
          f"(tuning took {t_tune:.1f}s, cached in "
          f"{os.environ['REPRO_PLAN_CACHE']})")
    assert err < 1e-3
    print("OK — all paths agree with the oracle")


if __name__ == "__main__":
    main()
