"""Mesh construction for the production topology.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 CPU device; only
dryrun.py sets XLA_FLAGS for 512 placeholder devices before any import.

Topology (DESIGN.md §5):
  single pod : (data=16, model=16)            = 256 chips  (TPU v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips
The `pod` axis composes with `data` for batch/FSDP sharding, so adding pods
widens DP without touching the in-pod layout — elastic scaling is a config
change and checkpoints are mesh-agnostic (checkpoint/store.py).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2) -> Mesh:
    """Single pod (16x16) or N pods x (16x16).  Scaling pods widens the
    (pod, data) batch/FSDP dimension only — the in-pod layout is
    untouched, which is what makes pod count an elastic knob."""
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 1, model: int = 1) -> Optional[Mesh]:
    """A small mesh over however many local devices exist (tests); None if
    a single device (model code then runs with constraints disabled)."""
    n = len(jax.devices())
    if n < data * model:
        return None
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_chips(mesh: Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
