"""In-graph activation sharding constraints.

GSPMD's sharding propagation is weak through ``lax.scan`` (replicated carry
inits win the fixpoint), so the model code pins activation shardings at
block boundaries and on attention scan carries. Outside a mesh context
(small CPU tests) these are no-ops.

Logical dims: 'batch' -> ('pod','data') subset present in the mesh;
'model' -> 'model' when it divides the dim; None -> replicated.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _current_mesh():
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def constrain(x, *dims):
    """dims: per-axis logical name ('batch' | 'model' | 'ep' | None).

    'ep' shards one dim over ('model', pod?, 'data') jointly — the expert-
    parallel row layout (expert-major outer, token rows inner)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = []
    for size, d in zip(x.shape, dims):
        if d == "batch":
            axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            spec.append(axes if (axes and size % n == 0) else None)
        elif d == "model" and "model" in mesh.axis_names:
            spec.append("model" if size % mesh.shape["model"] == 0 else None)
        elif d in ("ep", "ept") and "model" in mesh.axis_names:
            dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            axes = (("model",) + dp) if d == "ep" else (dp + ("model",))
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            spec.append(axes if size % n == 0 else None)
        else:
            spec.append(None)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_tree(tree, *dims):
    return jax.tree.map(lambda l: constrain(l, *dims), tree)


def model_axis_size():
    """Size of the 'model' mesh axis in the current context (0 if none)."""
    mesh = _current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 0
    return mesh.shape["model"]
