"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run launcher sets the
host-device count env var before any jax import.

``make_allocated_mesh`` additionally orders the device list by one of the
paper's allocation strategies over the HyperX fleet (fabric.placement), so
mesh axes land on physical endpoints with known PB/distance properties.
"""

from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # jax >= 0.7 makes mesh axes Explicit by default; the model code
    # shards through with_sharding_constraint, which needs Auto axes
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_allocated_mesh(strategy: str = "diagonal", *, multi_pod: bool = False,
                        seed: int = 0):
    """(Mesh, HyperXPlacement) with allocation-ordered devices."""
    from repro.fabric.placement import make_placed_mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_placed_mesh(strategy, shape, axes, seed=seed)
