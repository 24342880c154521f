"""Production mesh builders. Functions, not module constants — importing
this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the step functions place activations with
    # with_sharding_constraint, which refuses Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(dp: int, tp: int, pods: int = 1):
    """Elastic mesh builder for arbitrary DP/TP splits (--dp/--tp)."""
    if pods > 1:
        return _mesh((pods, dp, tp), ("pod", "data", "model"))
    return _mesh((dp, tp), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Mesh axes that carry data parallelism (pod axis folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh):
    return "model" if "model" in mesh.axis_names else None
