"""The benchmark's yardstick: the plain periodic reference and the work counts.

Everything here is computed from a configuration's own taps and grid, never
from the program under test, so no change to the program can move it.

* ``reference_steps`` advances a field by Jacobi steps of a constant-
  coefficient stencil with periodic boundaries, in plain ``jax.numpy``:
  ``y[i] = sum_t c_t * x[i + o_t]``.  The grid is extended by periodic
  ghost zones of ``steps * r`` cells, stepped, and cropped.
* ``mesh_reference_steps`` does the same for a field split over the
  harness's own mesh (``field_mesh``): each shard fetches its ghost zones
  from its ring neighbours, so no device ever holds the whole grid.
* ``least_ops_per_point`` counts what one point-update needs at least:
  one multiply per distinct coefficient and one add per further tap.
* ``least_bytes_per_call`` is one read and one write of the grid.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

Taps = Sequence[tuple[Sequence[int], float]]


def taps_from_config(cfg: dict) -> tuple[tuple[tuple[int, ...], float], ...]:
    """The configuration's ``taps`` as ((offset, coeff), ...)."""
    return tuple((tuple(int(o) for o in off), float(c))
                 for off, c in cfg["taps"])


def radius(taps: Taps) -> int:
    return max(abs(o) for off, _ in taps for o in off)


def points(shape: Sequence[int]) -> int:
    return int(np.prod(shape))


def least_ops_per_point(taps: Taps) -> int:
    """Multiplies by each distinct coefficient plus the adds that join the
    taps: the least arithmetic one point-update needs (taps that share a
    coefficient are summed before one multiply)."""
    distinct = len({float(c) for _, c in taps})
    return distinct + len(taps) - 1


def least_ops_per_call(taps: Taps, shape: Sequence[int], steps: int) -> int:
    return least_ops_per_point(taps) * points(shape) * steps


def least_bytes_per_call(shape: Sequence[int], itemsize: int) -> int:
    """One read and one write of the grid: what any plan has to move."""
    return 2 * points(shape) * itemsize


def _step_inner(x: jax.Array, taps: Taps, h: int) -> jax.Array:
    """One step on the cells at least ``h`` (>= r) from every edge of a
    ghost-extended slab; the outer ring keeps its values.  Updating in
    place keeps the reference at about two slabs of memory."""
    acc = None
    for off, c in taps:
        sl = tuple(slice(h + o, n - h + o) for o, n in zip(off, x.shape))
        term = x[sl] * jnp.asarray(c, x.dtype)
        acc = term if acc is None else acc + term
    return x.at[tuple(slice(h, n - h) for n in x.shape)].set(acc)


def _wrap(x: jax.Array, axis: int, h: int) -> jax.Array:
    n = x.shape[axis]
    return jnp.concatenate([jax.lax.slice_in_dim(x, n - h, n, axis=axis), x,
                            jax.lax.slice_in_dim(x, 0, h, axis=axis)],
                           axis=axis)


@partial(jax.jit, static_argnums=(1, 2, 3))
def reference_steps(x: jax.Array, taps: tuple, steps: int,
                    compute_dtype: str | None = None) -> jax.Array:
    """``x`` advanced by ``steps`` periodic Jacobi steps of ``taps``.

    ``compute_dtype`` (default: ``x``'s) is the precision the steps run
    in; the result comes back in ``x``'s dtype.  The grid is extended by
    ``steps * r`` wrapped ghost cells per side, stepped and cropped: step
    ``t`` leaves exact values at least ``t * r`` cells inside the slab, so
    the cropped block is exact."""
    out_dtype = x.dtype
    r = radius(taps)
    h = steps * r
    v = x.astype(jnp.dtype(compute_dtype) if compute_dtype else x.dtype)
    for axis in range(v.ndim):
        if h > v.shape[axis]:
            raise ValueError(f"ghost zone {h} wider than the extent "
                             f"{v.shape[axis]} on axis {axis}")
        v = _wrap(v, axis, h)
    v = jax.lax.fori_loop(0, steps, lambda _, u: _step_inner(u, taps, r), v)
    return v[tuple(slice(h, n - h) for n in v.shape)].astype(out_dtype)


def field_mesh(shards: Sequence[int], devices=None) -> Mesh:
    """The harness's mesh for a field cut into ``shards[i]`` blocks along
    spatial axis ``i``: one mesh axis ``a<i>`` per spatial axis, Auto
    axes, over the first ``prod(shards)`` of ``devices`` (default: all)."""
    shards = tuple(int(s) for s in shards)
    devs = jax.devices() if devices is None else list(devices)
    n = points(shards)
    if n > len(devs):
        raise ValueError(f"mesh {list(shards)} needs {n} devices, "
                         f"have {len(devs)}")
    return Mesh(np.asarray(devs[:n]).reshape(shards),
                tuple(f"a{i}" for i in range(len(shards))),
                axis_types=(AxisType.Auto,) * len(shards))


def field_sharding(mesh: Mesh) -> NamedSharding:
    """Spatial axis ``i`` split over mesh axis ``i``."""
    return NamedSharding(mesh, PartitionSpec(*mesh.axis_names))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def mesh_reference_steps(x: jax.Array, taps: tuple, steps: int, mesh: Mesh,
                         compute_dtype: str | None = None) -> jax.Array:
    """``reference_steps`` on a field laid out by ``field_sharding(mesh)``.

    Each shard is placed in a slab with ``h = steps * r`` ghost cells per
    side, filled one spatial axis after another: a split axis fetches
    them from its ring neighbours by ``ppermute``, a whole axis wraps onto
    itself (the strips are the shard's own).  A later axis's strips span
    the ghosts already filled, so the corners come along.  The slab is
    written in place, not concatenated, so a shard holds about two slabs
    beside its input.  It is then stepped and cropped as in
    ``reference_steps``; the result keeps ``x``'s layout and dtype."""
    out_dtype = x.dtype
    r = radius(taps)
    h = steps * r
    names = mesh.axis_names
    spec = PartitionSpec(*names)

    def local(v):
        v = v.astype(jnp.dtype(compute_dtype) if compute_dtype else v.dtype)
        inner = tuple(slice(h, n + h) for n in v.shape)
        slab = jnp.zeros(tuple(n + 2 * h for n in v.shape), v.dtype)
        slab = slab.at[inner].set(v)
        for axis, name in enumerate(names):
            n, m = mesh.shape[name], v.shape[axis]
            if h > m:
                raise ValueError(f"ghost zone {h} wider than the shard "
                                 f"extent {m} on axis {axis}")

            def at(sl):    # earlier axes whole (ghosts filled), later inner
                return (slice(None),) * axis + (sl,) + inner[axis + 1:]
            low, high = slab[at(slice(m, m + h))], slab[at(slice(h, 2 * h))]
            if n > 1:
                ring = range(n)
                low = jax.lax.ppermute(low, name,
                                       [(i, (i + 1) % n) for i in ring])
                high = jax.lax.ppermute(high, name,
                                        [(i, (i - 1) % n) for i in ring])
            slab = (slab.at[at(slice(0, h))].set(low)
                    .at[at(slice(m + h, m + 2 * h))].set(high))
        slab = jax.lax.fori_loop(0, steps,
                                 lambda _, u: _step_inner(u, taps, r), slab)
        return slab[inner].astype(out_dtype)
    return jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)(x)


@jax.jit
def rel_err(y: jax.Array, ref: jax.Array) -> jax.Array:
    """max |y - ref| / max |ref|, in float32; NaN or inf in ``y`` gives inf."""
    y32, r32 = y.astype(jnp.float32), ref.astype(jnp.float32)
    err = jnp.max(jnp.abs(y32 - r32))
    err = jnp.where(jnp.all(jnp.isfinite(y32)), err, jnp.inf)
    return err / jnp.max(jnp.abs(r32))
