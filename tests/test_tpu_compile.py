"""Compile-only rehearsals of the main stencil kernels for a TPU v5e.

The TPU compiler compiles for a described chip that is not attached
(``jax.experimental.topologies``), so Mosaic's refusals — an op it does
not lower, a tile over the VMEM limit — surface here at the paper's
sizes instead of on the chip.  Nothing runs, so nothing here checks
results; interpret-mode tests do that.  The topology is described inside
a fixture (never at import): only the worker given this file loads the
TPU library, and where it cannot be described the tests skip.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import stencils
from repro.kernels import ops

N1D = 10_244_096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, shape, dtype, sharding):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    lowered = jax.jit(fn).lower(x)
    return lowered, lowered.compile()


@pytest.mark.parametrize("name,shape,dtype,ttile", [
    ("1d3p", (N1D,), jnp.float32, 1),
    ("1d5p", (N1D,), jnp.float32, 4),
    ("1d3p", (N1D,), jnp.bfloat16, 1),
    ("2d9p", (3072, 3072), jnp.bfloat16, 1),
    ("3d27p", (256, 256, 256), jnp.float32, 1),
])
def test_resident_sweep_compiles_to_mosaic(one_chip, name, shape, dtype,
                                           ttile):
    """One depth-k·ttile resident sweep (layout in, kernel, layout out)
    compiles, and the kernel is a Mosaic custom call."""
    spec = stencils.make(name)
    k = 2
    _, compiled = _compile(
        lambda v: ops.stencil_sweep_periodic(
            spec, v, k * ttile, k=k, ttile=ttile, interpret=False),
        shape, dtype, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_mxu_sweep_compiles_at_highest_precision(one_chip):
    """The f32 MXU sweep compiles, and its contraction asks for HIGHEST
    precision (the TPU's default would round f32 operands to bf16).  A
    256-point operator tile keeps the embedded band table, and so the
    compile, small."""
    spec = stencils.make("2d5p")
    lowered, compiled = _compile(
        lambda v: ops.stencil_sweep_mxu(spec, v, 2, k=2, vl=128, m=2),
        (3072, 3072), jnp.float32, one_chip)
    assert "HIGHEST" in lowered.as_text()
    assert compiled.as_text()


_KERNEL_TAG = re.compile(
    r'custom_call_target="tpu_custom_call"[^\n]*?'
    r'kernel_metadata=\{\s*"repro"\s*:\s*"(\w+)"\s*\}')


@pytest.mark.parametrize("name,shape,steps,tags", [
    # two depth-4 sweeps (a chunk of two leaves no loop), one
    # single-step remainder sweep
    ("2d5p", (64, 1024), 9, ["sweep", "sweep", "sweep"]),
    # the Pallas block transposes in and out of the layout, two sweeps
    ("1d3p", (1 << 16,), 8, ["layout", "layout", "sweep", "sweep"]),
])
def test_every_kernel_launch_is_tagged(one_chip, name, shape, steps, tags):
    """Every Mosaic call of the resident run path carries its layer in
    ``kernel_metadata``, which the device trace shows and the benchmark's
    metrics select by."""
    spec = stencils.make(name)
    _, compiled = _compile(
        lambda v: ops._sweep_periodic_impl(spec, v, steps, 4, None, None,
                                           None, "fused", False),
        shape, jnp.float32, one_chip)
    text = compiled.as_text()
    assert sorted(_KERNEL_TAG.findall(text)) == tags
    assert text.count('custom_call_target="tpu_custom_call"') == len(tags)


# a copy whose operand is a value of the loop's state: the carried field
# copied into a fresh buffer before a launch
_CARRY_COPY = re.compile(
    r"= \w+\[([\d,]*)\]\S* copy\(%get-tuple-element")


@pytest.mark.parametrize("launches", [2, 3, 4, 5])
@pytest.mark.parametrize("name,shape", [
    ("1d3p", (1 << 16,)),
    ("2d5p", (64, 1024)),
])
def test_sweep_loop_copies_no_carried_field(one_chip, name, shape,
                                            launches):
    """One chunk of 2-5 depth-4 launches hands the field from launch to
    launch without copying it: each launch writes the buffer the launch
    before it read, so the loop needs no copy of its carry (an odd last
    launch is peeled)."""
    spec = stencils.make(name)
    _, compiled = _compile(
        lambda v: ops._sweep_periodic_impl(spec, v, 4 * launches, 4, None,
                                           None, None, "fused", False),
        shape, jnp.float32, one_chip)
    text = compiled.as_text()
    size = math.prod(shape)
    carried = [dims for dims in _CARRY_COPY.findall(text)
               if math.prod(int(d) for d in dims.split(",")) == size]
    assert carried == [], carried
