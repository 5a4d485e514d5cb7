"""Host mesh definitions.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init)."""
from __future__ import annotations

import warnings
from typing import Tuple

import jax
from jax.sharding import AxisType


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """(data, model) mesh over the first data*model local devices, with
    Auto axes (sharding follows annotations and GSPMD propagation).

    A shape that needs more devices than exist raises: a sharded run on
    fewer chips than asked for would report numbers for a mesh nobody
    requested."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh (data, model)=({data}, {model}) needs "
                         f"{data * model} devices; only {n} available")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def parse_mesh(flag: str) -> Tuple[int, int]:
    """Parse a ``--mesh DATA,MODEL`` flag value (e.g. ``2,4``)."""
    try:
        data, model = (int(v) for v in flag.split(","))
    except ValueError:
        raise ValueError(
            f"--mesh expects 'DATA,MODEL' (e.g. 2,4), got {flag!r}") from None
    if data < 1 or model < 1:
        raise ValueError(f"--mesh sizes must be >= 1, got {flag!r}")
    return data, model


def mesh_for_plan(plan, data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Build the (data, model) host mesh an EpitomePlan will serve on, and
    legalize the plan's placement annotations against it: every annotated
    axis must exist in the mesh and divide the layer's (m, n) — offenders
    are reported (they degrade to replicated at the array layer).  Returns
    the mesh; the plan object is not mutated, so the same artifact can be
    re-checked against a different mesh."""
    from ..pim.plan import legalize_placements
    mesh = make_host_mesh(data=data, model=model)
    shape = dict(mesh.shape)
    _, report = legalize_placements(plan, shape)
    for name, reasons in report.items():
        warnings.warn(f"plan {plan.arch!r} layer {name!r}: "
                      + "; ".join(reasons), stacklevel=2)
    return mesh

