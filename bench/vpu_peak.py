"""The chip's vector-unit (VPU) arithmetic rate, measured in the run.

No TPU document publishes the VPU's rate, and stencil arithmetic runs
there, not on the matrix unit.  This kernel keeps ``CHAINS`` independent
``(8, 128)`` tiles resident in VMEM and applies ``v * a + b`` to each,
``iters`` times: two operations per element per iteration, with no memory
traffic inside the loop and enough independent chains to hide latency.
The rate is operations over the host-clock time of a call that lasts some
hundred milliseconds, the best of a few calls.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CHAINS = 32
TILE = (8, 128)
ITERS = 1 << 22
UNROLL = 8


def _kernel(x_ref, o_ref, *, iters: int):
    a = jnp.asarray(0.9990234375, x_ref.dtype)   # exact in bf16 and f32
    b = jnp.asarray(0.0009765625, x_ref.dtype)
    vs = tuple(x_ref[pl.ds(i * TILE[0], TILE[0]), :] for i in range(CHAINS))

    def body(_, v):
        for _ in range(UNROLL):       # Mosaic unrolls no loop by itself
            v = tuple(u * a + b for u in v)
        return v
    vs = jax.lax.fori_loop(0, iters // UNROLL, body, vs)
    for i, v in enumerate(vs):
        o_ref[pl.ds(i * TILE[0], TILE[0]), :] = v


@functools.partial(jax.jit, static_argnums=(1,))
def chains(x: jax.Array, iters: int) -> jax.Array:
    return pl.pallas_call(
        functools.partial(_kernel, iters=iters),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=jax.default_backend() != "tpu",
        name="bench_vpu_peak")(x)


def ops_per_call(iters: int) -> int:
    return 2 * (iters // UNROLL * UNROLL) * CHAINS * TILE[0] * TILE[1]


def measure(dtype, iters: int | None = None, repeats: int = 3) -> float:
    """Operations per second of the VPU for ``dtype`` (best of
    ``repeats`` calls of ``iters`` iterations, default ``ITERS``, after
    one warm call)."""
    iters = iters or ITERS
    x = jnp.ones((CHAINS * TILE[0], TILE[1]), dtype)
    jax.block_until_ready(chains(x, iters))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chains(x, iters))
        best = min(best, time.perf_counter() - t0)
    return ops_per_call(iters) / best
