"""The mesh API seam for the sharded read path.

Every shard_map in parallel/ goes through :func:`shard_map` /
:func:`varying` below. The installed JAX types values inside a
``jax.shard_map`` body as replicated or device-varying per mesh axis
(``check_vma``): a loop whose body returns device-varying values needs
initial carries of the same type, which is what :func:`varying` marks.
"""

from __future__ import annotations

import jax


def shard_map(body, mesh, in_specs, out_specs, check_vma: bool = True):
    """``check_vma=False`` for a body that holds a ``pallas_call``: the
    interpreter that runs a kernel where the backend is no TPU does not
    type its own values by mesh axis, so the body goes unchecked (and
    needs no :func:`varying`)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def varying(x, axes):
    """``x`` typed device-varying over every axis of ``axes`` (a value
    already varying over some of them only gains the rest) — the initial
    value of a loop carry whose body output depends on the shard."""
    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, need, to="varying") if need else x
