"""The JAX API seams the SPMD stack goes through.

Every module under ``repro/`` calls the wrappers here instead of touching
these APIs directly (rule ``compat-api`` in ``repro.analysis``, asserted by
``tests/test_compat.py``), so a future JAX upgrade that moves one of them
changes this file only:

  * ``jax.shard_map`` and its ``check_vma`` kwarg;
  * ``jax.lax.axis_size``;
  * ``jax.make_mesh`` with ``jax.sharding.AxisType`` axis types;
  * ``Compiled.cost_analysis()``, normalized to one flat dict.

The repository targets the JAX pinned in ``requirements-dev.txt``; there
is no branch for any other version.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool = True) -> Callable:
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name) -> int:
    """Static size of a mapped mesh axis."""
    return jax.lax.axis_size(axis_name)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None):
    """``jax.make_mesh`` with Auto axis types."""
    kwargs: Dict[str, Any] = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
        **kwargs)


def cost_analysis(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a flat dict; a backend that reports
    no analysis (None) gives ``{}``.  Errors raise."""
    raw = compiled.cost_analysis()
    return {} if raw is None else dict(raw)
