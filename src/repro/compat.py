"""Thin wrappers over the jax API surface the repo calls in many places.

* ``shard_map`` — ``jax.shard_map`` with replication checking off.
* ``Compiled.cost_analysis()`` — normalized to a flat dict.
"""
from __future__ import annotations

from typing import Any

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def cost_analysis_dict(cost: Any) -> dict:
    """Normalize ``compiled.cost_analysis()`` to a flat {metric: value} dict."""
    return dict(cost) if cost else {}
