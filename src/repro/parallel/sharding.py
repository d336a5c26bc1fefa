"""Sharding rules: FSDP('data') x TP('model') (+ 'pod' data parallelism).

Every parameter gets a PartitionSpec by shape heuristics with divisibility
checks (a dim is only sharded if divisible by the axis size); optimizer state
inherits the parameter's spec (ZeRO-3 comes for free under pjit).  Activations
are sharded batch-over-('pod','data') via the input specs; intermediate
shardings propagate.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with Auto axis types, the one kind the serving and launch
    meshes use.  ``jax.make_mesh`` builds Explicit axes, which
    ``with_sharding_constraint`` refuses and on which the host-side bucket
    slicing of a sharded output has no unambiguous sharding."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * mesh.devices.ndim)


def axis_size(mesh: Mesh, name) -> int:
    """Product of the named axes' sizes; an axis absent from the mesh counts
    as 1, so the sharding rules degrade gracefully on reduced meshes (e.g. a
    data-only serving mesh has no 'model' axis — TP just becomes a no-op)."""
    if isinstance(name, (tuple, list)):
        return int(np.prod([mesh.shape.get(n, 1) for n in name]))
    return mesh.shape.get(name, 1)


def param_spec(path: str, shape, mesh: Mesh, *, fsdp_axis="data",
               tp_axis="model", min_size_fsdp: int = 2 ** 18) -> P:
    """Choose a spec for one parameter.

    Policy (matmul weights are ~2D (in, out), possibly with leading stack/
    expert dims):
      * last dim  -> TP axis   (column parallel) when divisible
      * second-to-last dim -> FSDP axis when divisible and tensor is large
      * leading scan/expert dims stay unsharded (scan slices them)
    Embeddings shard vocab over TP.  Norms/bias/small tensors replicate.
    """
    ndim = len(shape)
    # an axis absent from the mesh (or of size 1) is never *named* in a
    # spec — naming an unknown axis makes NamedSharding raise — so on
    # reduced meshes (e.g. a data-only serving mesh) TP/FSDP degrade to
    # no-ops instead of crashing
    tp = axis_size(mesh, tp_axis)
    fsdp = axis_size(mesh, fsdp_axis)
    size = int(np.prod(shape))
    spec = [None] * ndim
    if ndim == 0 or size < 2 ** 14:
        return P(*spec)
    if "embed" in path and ndim == 2:
        # (V, d): shard d over TP so the token gather (and its scatter-add
        # gradient) stays device-local; the logits matmul re-constrains a
        # vocab-sharded view (models/transformer.loss paths).  Sharding the
        # gather's vocab dim makes XLA SPMD replicate the table (observed:
        # "Involuntary full rematerialization" warnings + GB-scale gathers).
        if tp > 1 and shape[1] % tp == 0:
            spec[1] = tp_axis
        return P(*spec)
    if ndim >= 2:
        if tp > 1 and shape[-1] % tp == 0:
            spec[-1] = tp_axis
        if fsdp > 1 and size >= min_size_fsdp:
            if shape[-2] % fsdp == 0:
                spec[-2] = fsdp_axis
            elif shape[-1] % (tp * fsdp) == 0:
                # last-dim fallback: stack FSDP onto the TP dim, or take the
                # last dim alone when TP is degenerate (tp == 1 — a spec must
                # never name a size-1/absent axis)
                spec[-1] = (fsdp_axis, tp_axis) if spec[-1] is not None \
                    else fsdp_axis
        return P(*spec)
    # 1D big vectors (e.g. stacked biases): replicate
    return P(*spec)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def params_shardings(param_tree, mesh: Mesh, **kw):
    """Map a pytree of arrays/ShapeDtypeStructs to NamedShardings."""
    def one(path, leaf):
        spec = param_spec(_path_str(path), leaf.shape, mesh, **kw)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, param_tree)


def replicated_shardings(param_tree, mesh: Mesh):
    """Map every leaf to a fully-replicated NamedSharding on ``mesh``.

    Serving replica pools use this for the weight pytree: each device holds
    a complete copy (the analogue of every replicated FPGA pipeline keeping
    its weights in its own BRAM), so any replica can serve any batch with no
    collective on the critical path.  Contrast ``params_shardings``, which
    FSDP/TP-shards large tensors for training."""
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()),
                                  param_tree)


# logical input axes -> mesh axes
def input_sharding_factory(mesh: Mesh):
    """Returns sharding(axes_tuple) for configs.base.input_specs.

    'batch' -> ('pod','data') when batch divisible, else unsharded (the seq
    dim takes 'data' for batch-1 long-context cells); 'heads'/'embed' ->
    'model' when divisible."""
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)

    def sharding(shape, axes):
        spec = []
        used_data = False
        model_n = axis_size(mesh, "model")
        for dim, ax in zip(shape, axes):
            if ax == "batch":
                n = axis_size(mesh, batch_axes)
                if batch_axes and dim % n == 0:
                    spec.append(batch_axes if len(batch_axes) > 1
                                else batch_axes[0])
                    used_data = True
                else:
                    spec.append(None)
            elif ax == "seq":
                if batch_axes and not used_data and \
                        dim % axis_size(mesh, batch_axes) == 0:
                    # sequence sharding fallback (batch-1 long-context cells)
                    spec.append(batch_axes if len(batch_axes) > 1
                                else batch_axes[0])
                    used_data = True
                else:
                    spec.append(None)
            elif ax in ("heads", "embed"):
                spec.append("model" if model_n > 1 and dim % model_n == 0
                            else None)
            else:
                spec.append(None)
        return NamedSharding(mesh, P(*spec))

    return sharding
