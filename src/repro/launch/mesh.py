"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16x16 = 256 chips per pod ("data", "model"); multi-pod
adds a leading "pod" axis (2 x 16 x 16 = 512 chips). The dry-run forces 512
host devices via XLA_FLAGS (see launch/dryrun.py lines 1–2). Every mesh has
Auto axes (``sharding/mesh.py``).
"""
from __future__ import annotations

from repro.sharding.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_for_chips(chips: int, model_axis: int = 16, *,
                   pod_size: int = 256):
    """Auto-provisioner search points: chips -> (pod?, data, model) mesh.
    Chips beyond one pod add a 'pod' axis (inter-pod = DP)."""
    if chips <= pod_size:
        model = min(model_axis, chips)
        data = chips // model
        return make_mesh((data, model), ("data", "model"))
    pods = chips // pod_size
    model = model_axis
    data = pod_size // model
    return make_mesh((pods, data, model), ("pod", "data", "model"))
