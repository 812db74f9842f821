"""2-D heat equation end-to-end: physics + tessellate tiling + (optionally)
the distributed halo runtime.

    PYTHONPATH=src python examples/heat_equation_2d.py

Evolves a hot square on a cold plate with the 2d5p diffusion stencil,
verifies conservation + convergence to the mean, and cross-checks the
tessellate tiler against plain stepping."""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import stencils, tessellate

N, STEPS, H = 256, 128, 4


def main():
    compile_cache.enable()
    spec = stencils.make("heat2d")
    x = jnp.zeros((N, N), jnp.float32)
    x = x.at[120:136, 120:136].set(100.0)     # hot square (16×16:
    # small enough that diffusion measurably erodes its center —
    # diffusion length √STEPS ≈ 11 > half-width 8)
    total0 = float(jnp.sum(x))

    plain = stencils.apply_steps(spec, x, STEPS)
    tiled = tessellate.tessellate_run(spec, x, STEPS, tile=(64, 64),
                                      height=H)
    err = float(jnp.max(jnp.abs(plain - tiled)))
    print(f"tessellate vs plain stepping: max_err={err:.2e}")
    assert err < 1e-3

    total = float(jnp.sum(tiled))
    print(f"heat conserved: {total0:.1f} → {total:.1f}")
    assert abs(total - total0) / total0 < 1e-5

    peak0, peak = float(jnp.max(x)), float(jnp.max(tiled))
    print(f"peak temperature diffused: {peak0:.1f} → {peak:.2f}")
    assert peak < peak0

    center_mass0 = float(jnp.sum(x[124:132, 124:132]))
    center_mass = float(jnp.sum(tiled[124:132, 124:132]))
    assert center_mass < center_mass0        # heat spread outward
    print("OK — physics sane, tiling exact")


if __name__ == "__main__":
    main()
