"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first jax use).

Single pod:  (16, 16) over ("data", "model") = 256 chips (TPU v5e pod slice).
Multi-pod:   (2, 16, 16) over ("pod", "data", "model") = 512 chips; the
"pod" axis composes with "data" for batch/gradient parallelism (DCN-friendly
— one gradient all-reduce per step crosses pods), while "model" (TP/EP)
stays inside a pod on ICI.
"""

from __future__ import annotations

from jax.experimental import mesh_utils
from jax.sharding import Mesh


def _mesh(shape, axes) -> Mesh:
    # Mesh() keeps every axis in Auto sharding mode (jax.make_mesh would
    # default to Explicit)
    return Mesh(mesh_utils.create_device_mesh(shape), axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (device count set by the test's XLA_FLAGS)."""
    return _mesh(shape, axes)
