"""Ambient mesh context so model code can express sharding constraints
without threading the mesh object through every call."""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_MESH = None


def set_mesh(mesh):
    global _MESH
    _MESH = mesh


def get_mesh():
    return _MESH


@contextlib.contextmanager
def mesh_context(mesh):
    global _MESH
    prev, _MESH = _MESH, mesh
    try:
        with mesh:
            yield mesh
    finally:
        _MESH = prev


def batch_axes():
    if _MESH is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in _MESH.axis_names)


def sharded_take(emb, tokens):
    """Embedding lookup with the table sharded P(None, 'model') (d-sharded).

    Plain jnp.take over a last-dim-sharded table trips XLA SPMD ("slice dim
    size greater than dynamic slice dimension"); a shard_map makes the gather
    explicitly local per model shard.  Gradient (scatter-add) is local too."""
    if _MESH is None or "model" not in _MESH.axis_names or \
            emb.shape[1] % _MESH.shape["model"] != 0:
        return jax.numpy.take(emb, tokens, axis=0)
    from jax import shard_map
    ba = batch_axes()
    import numpy as np
    nb = int(np.prod([_MESH.shape[a] for a in ba])) if ba else 1
    tspec = P(ba if len(ba) > 1 else (ba[0] if ba else None), None) \
        if ba and tokens.shape[0] % nb == 0 else P(None, None)
    ospec = P(*tspec, "model")

    def f(e_loc, t_loc):
        return jax.numpy.take(e_loc, t_loc, axis=0)

    return shard_map(f, mesh=_MESH,
                     in_specs=(P(None, "model"), tspec),
                     out_specs=ospec)(emb, tokens)


def constrain(x, *spec):
    """with_sharding_constraint against the ambient mesh (no-op without one).
    Axis entries that don't divide the corresponding dim are dropped."""
    if _MESH is None:
        return x
    fixed = []
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in _MESH.axis_names)
        import numpy as np
        n = int(np.prod([_MESH.shape[a] for a in axes])) if axes else 1
        fixed.append((axes if len(axes) > 1 else axes[0])
                     if axes and dim % n == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*fixed)))
