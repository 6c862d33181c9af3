"""Mesh-aware sharding constraints for model code.

``constrain(x, *axes)`` applies with_sharding_constraint for the axes that
exist in the ambient mesh AND divide the corresponding dim.  With no
ambient mesh (one device: tests, one-chip serving) it returns ``x``
unchanged; under a mesh (the dry run's ``jax.set_mesh``) a constraint
that cannot be applied raises.

The BATCH sentinel expands to ('pod','data') / 'data' as available.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, PartitionSpec as P

BATCH = "__batch__"


def _mesh_axes():
    """Auto axes of the ambient mesh as ``{name: size}``; {} off-mesh.

    Inside shard_map the manual axes must not appear in sharding
    constraints, so only Auto axes count.
    """
    am = jax.sharding.get_abstract_mesh()
    return {n: s for n, s, t in zip(am.axis_names, am.axis_sizes,
                                    am.axis_types)
            if t == AxisType.Auto}


def constrain(x, *spec):
    axes = _mesh_axes()
    if not axes:
        return x
    resolved = []
    for dim, a in zip(x.shape, spec):
        if a == BATCH:
            a = tuple(n for n in ("pod", "data") if n in axes) or None
            if isinstance(a, tuple) and len(a) == 1:
                a = a[0]
        if a is None:
            resolved.append(None)
            continue
        names = a if isinstance(a, tuple) else (a,)
        fits = (all(n in axes for n in names)
                and dim % math.prod(axes[n] for n in names) == 0)
        resolved.append(a if fits else None)
    resolved += [None] * (x.ndim - len(resolved))
    return jax.lax.with_sharding_constraint(x, P(*resolved))
